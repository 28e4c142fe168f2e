"""Model-zoo subsystem: meta-arch registry, head graphs, detection heads
and neuromorphic event streams.

Contracts pinned here (the CI api-surface job runs this file as the
``-m zoo`` fast lane):

* **Registry** — ``register_arch`` / ``build_model`` round-trip, arch
  stamping for telemetry, message-asserted error paths (duplicate
  registration, unknown arch, missing ``arch`` key).
* **``fpca_cnn`` compatibility** — the zoo-built classifier is
  *byte-identical* to ``configs.fpca_cnn.make_model_program``: golden
  signature pin, bit-equal logits, and ZERO new compiles on a shared
  executable cache.
* **HeadGraph validation** — cycles, duplicate/reserved node names,
  undefined inputs, join-shape mismatches and bad outputs all fail at
  construction with node-named messages.
* **Residual / detection numerics** — compiled graph heads equal the
  dense-compose oracle (frontend counts -> ``apply_head``), per-tick AND
  skip-aware patched streaming, per-tick AND segment serving.
* **Shared-head fusion** — same-signature model configs of one launch are
  served by ONE vmapped head pass, bit-identical to the per-config path.
* **Event streams** — per-tick packets reconcile exactly with the gate's
  changed-block accounting; segment-reconstructed packets are identical to
  per-tick ones; ``fleet_report`` breaks workloads out per arch.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax

import repro.fpca as fpca
from repro.core import analysis
from repro.core.mapping import FPCASpec, active_window_mask, output_dims
from repro.fpca import zoo
from repro.models import heads
from repro.serving.fpca_pipeline import FPCAPipeline
from repro.serving.observe import assert_reconciled, fleet_report
from repro.serving.streaming import StreamServer

pytestmark = pytest.mark.zoo

H = W = 20


def _spec(c_o: int = 3) -> FPCASpec:
    return FPCASpec(image_h=H, image_w=W, out_channels=c_o, kernel=5, stride=5)


def _kernel(spec: FPCASpec, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    k = spec.kernel
    return (rng.normal(size=(spec.out_channels, k, k, spec.in_channels))
            * 0.2).astype(np.float32)


def _frames(n: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    f = rng.uniform(0, 1, (n, H, W, 3)).astype(np.float32)
    if n > 2:
        f[2] = f[1]          # one quiet tick exercises the zero-event path
    return f


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_round_trip():
    assert {"fpca_cnn", "fpca_resnet", "fpca_detect"} <= set(
        zoo.available_archs()
    )

    @zoo.register_arch("zoo_test_arch")
    def _build(cfg):
        return zoo._ARCHS["fpca_cnn"](cfg)

    try:
        model = zoo.build_model({"arch": "zoo_test_arch", "spec": _spec()})
        assert model.arch == "zoo_test_arch"     # stamped for telemetry
        assert "zoo_test_arch" in zoo.available_archs()
        # overwrite=True replaces; kwargs override cfg keys
        @zoo.register_arch("zoo_test_arch", overwrite=True)
        def _build2(cfg):
            return zoo._ARCHS["fpca_resnet"](cfg)

        model2 = zoo.build_model({"arch": "fpca_cnn"}, arch="zoo_test_arch",
                                 spec=_spec())
        assert model2.is_graph_head
    finally:
        zoo._ARCHS.pop("zoo_test_arch", None)


def test_registry_duplicate_rejected():
    with pytest.raises(ValueError, match=r"'fpca_cnn' already registered"):
        zoo.register_arch("fpca_cnn")(lambda cfg: None)


def test_registry_bad_names():
    with pytest.raises(ValueError, match="non-empty string"):
        zoo.register_arch("")
    with pytest.raises(KeyError, match=r"unknown architecture 'nope'"):
        zoo.build_model({"arch": "nope"})
    with pytest.raises(KeyError, match="needs an 'arch' key"):
        zoo.build_model({"spec": _spec()})


# ---------------------------------------------------------------------------
# fpca_cnn: byte-identical to the config module (golden pin, zero compiles)
# ---------------------------------------------------------------------------

GOLDEN_CNN_SIG = (
    "repro.fpca.model/1",
    "repro.fpca/1",
    ("spec", 20, 20, 3, 5, 5, 5, 3, 0, 1, 8),
    ("out_channels", 3),
    ("adc", 8, 1.0),
    ("enc", 16, 1.0),
    ("circuit", ("v_sat", 1.0), ("s0", 0.37), ("drive_a", 0.15),
     ("drive_b", -0.1), ("drive_c", 0.25), ("coupling", 0.15),
     ("kappa_r", 0.012), ("r_metal_mm", 0.0), ("fp_iters", 8.0)),
    ("head", ("dense", 64, "relu"), ("dense", 2, "")),
    ("input_scale", 1.0),
)


def test_fpca_cnn_signature_golden():
    """Exact pinned value: the zoo build keys the same executables as the
    config module — change only by bumping a version string deliberately."""
    model = zoo.build_model({"arch": "fpca_cnn", "spec": _spec()})
    assert model.signature() == GOLDEN_CNN_SIG
    assert model.arch == "fpca_cnn"


def test_fpca_cnn_matches_config_module(bucket_model):
    from repro.configs.fpca_cnn import make_model_program

    spec = _spec()
    legacy = make_model_program(spec)
    built = zoo.build_model({"arch": "fpca_cnn", "spec": spec})
    # the arch stamp is telemetry-only: signatures identical
    assert built.signature() == legacy.signature()

    kernel = _kernel(spec)
    hp = legacy.init_head(jax.random.PRNGKey(0))
    cache = fpca.ExecutableCache(capacity=8)
    m1 = fpca.compile(legacy, backend="basis", weights=kernel,
                      head_params=hp, model=bucket_model, cache=cache)
    images = _frames(2)
    out1 = np.asarray(m1.run(images))
    misses = cache.info().misses
    m2 = fpca.compile(built, backend="basis", weights=kernel,
                      head_params=hp, model=bucket_model, cache=cache)
    out2 = np.asarray(m2.run(images))
    # bit-identical logits, ZERO new compiles: the zoo build warm-hits every
    # executable the config-module build compiled
    np.testing.assert_array_equal(out1, out2)
    assert cache.info().misses == misses


# ---------------------------------------------------------------------------
# HeadGraph validation (message-asserted error paths)
# ---------------------------------------------------------------------------


def test_head_graph_cycle_rejected():
    with pytest.raises(ValueError, match="cycle"):
        heads.HeadGraph(
            nodes=(
                heads.Node("a", fpca.ConvSpec(4, 3, padding="SAME"), ("b",)),
                heads.Node("b", fpca.ConvSpec(4, 3, padding="SAME"), ("a",)),
                heads.Node("out", fpca.DenseSpec(2), ("b",)),
            ),
            output="out",
        )


def test_head_graph_duplicate_and_reserved_names():
    conv = fpca.ConvSpec(4, 3, padding="SAME")
    with pytest.raises(ValueError, match=r"duplicate node name 'a'"):
        heads.HeadGraph(
            nodes=(heads.Node("a", conv), heads.Node("a", conv, ("a",)),
                   heads.Node("out", fpca.DenseSpec(2), ("a",))),
            output="out",
        )
    with pytest.raises(ValueError, match="'input' is reserved"):
        heads.HeadGraph(
            nodes=(heads.Node("input", conv),
                   heads.Node("out", fpca.DenseSpec(2), ("input",))),
            output="out",
        )


def test_head_graph_undefined_input_and_output():
    conv = fpca.ConvSpec(4, 3, padding="SAME")
    with pytest.raises(ValueError, match=r"reads undefined input 'ghost'"):
        heads.HeadGraph(
            nodes=(heads.Node("a", conv, ("ghost",)),
                   heads.Node("out", fpca.DenseSpec(2), ("a",))),
            output="out",
        )
    with pytest.raises(ValueError, match=r"output 'missing' is not a node"):
        heads.HeadGraph(
            nodes=(heads.Node("out", fpca.DenseSpec(2)),),
            output="missing",
        )
    with pytest.raises(ValueError, match="DenseSpec .* or DetectSpec"):
        heads.HeadGraph(
            nodes=(heads.Node("a", conv),), output="a"
        )


def test_head_graph_join_shape_mismatch():
    # stem emits 4 channels, branch emits 6: residual add must refuse
    g = heads.HeadGraph(
        nodes=(
            heads.Node("stem", fpca.ConvSpec(4, 3, padding="SAME")),
            heads.Node("branch", fpca.ConvSpec(6, 3, padding="SAME"),
                       ("stem",)),
            heads.Node("join", heads.AddSpec(), ("stem", "branch")),
            heads.Node("out", fpca.DenseSpec(2), ("join",)),
        ),
        output="out",
    )
    with pytest.raises(
        ValueError, match=r"node 'join': residual add needs matching"
    ):
        g.shapes((4, 4, 3))
    with pytest.raises(ValueError, match="at least 2 inputs"):
        heads.Node("join", heads.AddSpec(), ("stem",))


def test_head_graph_param_binding_errors():
    model = zoo.build_model({"arch": "fpca_resnet", "spec": _spec()})
    params = model.init_head(jax.random.PRNGKey(0))
    bad = dict(params)
    bad.pop("logits")
    with pytest.raises(ValueError, match="do not match parameterized nodes"):
        model.bind_head_params(bad)
    bad = dict(params)
    bad["fc"] = {"w": np.zeros((3, 3), np.float32),
                 "b": np.zeros((3,), np.float32)}
    with pytest.raises(ValueError, match=r"head node 'fc'"):
        model.bind_head_params(bad)


def test_graph_head_shapes_and_flops():
    model = zoo.build_model({"arch": "fpca_resnet", "spec": _spec()})
    with pytest.raises(TypeError, match="chain heads"):
        model.head_shapes()
    shapes = model.head.shapes(model.frontend.out_shape)
    assert shapes["join"] == shapes["stem"]
    fl = analysis.head_flops(model)
    assert fl["macs"] > 0 and fl["params"] > 0
    assert any(row["layer"].startswith("join:") for row in fl["per_layer"])
    rep = analysis.head_report(model)
    assert rep["e_head"] > 0


# ---------------------------------------------------------------------------
# residual classifier: compiled == dense-compose oracle
# ---------------------------------------------------------------------------


def test_resnet_compiled_matches_oracle(bucket_model):
    spec = _spec()
    model = zoo.build_model({"arch": "fpca_resnet", "spec": spec,
                             "width": 4, "hidden": 8, "n_classes": 3})
    kernel = _kernel(spec)
    hp = model.init_head(jax.random.PRNGKey(1))
    m = fpca.compile(model, backend="basis", weights=kernel, head_params=hp,
                     model=bucket_model)
    fe = fpca.compile(model.frontend, backend="basis", weights=kernel,
                      model=bucket_model)
    images = _frames(2, seed=3)
    got = np.asarray(m.run(images))
    counts = np.asarray(fe.run(images))
    want = np.asarray(model.apply_head(hp, counts))
    np.testing.assert_array_equal(got, want)
    assert got.shape == (2, 3)


# ---------------------------------------------------------------------------
# detection: Detections struct, streaming, patched parity, segments
# ---------------------------------------------------------------------------


def _detect_setup(bucket_model, gate_threshold=0.05):
    spec = _spec()
    model = zoo.build_model({"arch": "fpca_detect", "spec": spec,
                             "width": 4, "n_classes": 3})
    kernel = _kernel(spec, seed=2)
    hp = model.init_head(jax.random.PRNGKey(2))
    pipe = FPCAPipeline(bucket_model, backend="basis")
    pipe.register("det", model, kernel, head_params=hp)
    server = StreamServer(
        pipe, fpca.DeltaGateConfig(threshold=gate_threshold, hysteresis=0,
                                   keyframe_interval=0),
    )
    return spec, model, kernel, hp, pipe, server


def test_detect_run_returns_detections(bucket_model):
    spec = _spec()
    model = zoo.build_model({"arch": "fpca_detect", "spec": spec,
                             "width": 4, "n_classes": 3})
    assert model.output_kind == "detections"
    assert model.detect_classes == 3
    m = fpca.compile(model, backend="basis", weights=_kernel(spec),
                     head_params=model.init_head(jax.random.PRNGKey(0)),
                     model=bucket_model)
    det = m.run(_frames(2))
    assert isinstance(det, heads.Detections)
    h_o, w_o = output_dims(spec)
    assert det.scores.shape == (2, h_o, w_o, 3)
    assert det.boxes.shape == (2, h_o, w_o, 4)
    assert det.class_map().shape == (2, h_o, w_o)
    top = heads.Detections(det.scores[0], det.boxes[0]).top_k(3)
    assert len(top) == 3 and {"cell", "class", "score", "box"} <= top[0].keys()


def test_detect_stream_patched_parity(bucket_model):
    """Skip-aware detection: every gated tick's per-cell map equals the
    dense-compose oracle (masked counts patched into the carried effective
    map, head applied) — the chain-head parity contract, now for graphs."""
    spec, model, kernel, hp, pipe, server = _detect_setup(bucket_model)
    server.add_stream("cam", "det")
    # localized motion: only the top-left quadrant moves after the keyframe,
    # so gated ticks keep a strict subset of the windows
    rng = np.random.default_rng(7)
    base = rng.uniform(0, 1, (H, W, 3)).astype(np.float32)
    frames = np.stack([base] * 5)
    for t in range(1, 5):
        frames[t, :10, :10] = rng.uniform(0, 1, (10, 10, 3))
    fe = fpca.compile(model.frontend, backend="basis", weights=kernel,
                      model=bucket_model)
    results = list(server.serve("cam", frames))
    assert any(0 < r.kept_windows < r.total_windows for r in results)
    eff = np.zeros(model.frontend.out_shape, np.float32)
    for frame, r in zip(frames, results):
        assert r.detections is not None
        if r.block_mask is None or r.block_mask.all():
            counts = np.asarray(fe.run(frame))
            window = np.ones(counts.shape[:2], bool)
        else:
            window = active_window_mask(spec, r.block_mask)
            counts = np.asarray(fe.run(frame, block_mask=r.block_mask))
        eff = np.where(window[..., None], counts, eff)
        want = np.asarray(model.apply_head(hp, eff[None]))[0]
        np.testing.assert_array_equal(r.logits, want,
                                      err_msg=f"tick {r.frame_idx}")
        np.testing.assert_array_equal(
            np.asarray(r.detections.scores), want[..., :3]
        )
        np.testing.assert_array_equal(
            np.asarray(r.detections.boxes), want[..., 3:]
        )
        assert r.predicted_class is None      # per-cell map, not a logit row


def test_detect_segment_matches_per_tick(bucket_model):
    frames = _frames(6, seed=9)
    _, _, _, _, pipe_a, srv_a = _detect_setup(bucket_model)
    srv_a.add_stream("cam", "det")
    per_tick = list(srv_a.serve("cam", frames))
    _, _, _, _, pipe_b, srv_b = _detect_setup(bucket_model)
    srv_b.add_stream("cam", "det")
    seg = srv_b.run_segment("cam", frames)
    assert len(seg) == len(per_tick)
    for a, b in zip(per_tick, seg):
        np.testing.assert_array_equal(a.logits, b.logits,
                                      err_msg=f"tick {a.frame_idx}")
        assert b.detections is not None


def test_detect_serve_requests(bucket_model):
    """Pipeline serve(): detection configs resolve to Detections."""
    from repro.serving.fpca_pipeline import FrontendRequest

    _, model, _, _, pipe, _ = _detect_setup(bucket_model)
    frame = _frames(1)[0]
    out = pipe.serve([FrontendRequest("det", frame)])
    assert isinstance(out[0], heads.Detections)
    assert out[0].n_classes == 3


# ---------------------------------------------------------------------------
# shared-head fusion
# ---------------------------------------------------------------------------


def test_fused_shared_heads_bit_parity(bucket_model):
    """Two model configs sharing a model signature on one stream run ONE
    fused head pass a tick, bit-identical to serving each config alone."""
    spec = _spec()
    model = zoo.build_model({"arch": "fpca_resnet", "spec": spec,
                             "width": 4, "hidden": 8})
    kernel = _kernel(spec)
    hp = {"a": model.init_head(jax.random.PRNGKey(3)),
          "b": model.init_head(jax.random.PRNGKey(4))}
    frames = _frames(4, seed=11)

    def serve(configs):
        pipe = FPCAPipeline(bucket_model, backend="basis")
        for name in configs:
            pipe.register(name, model, kernel, head_params=hp[name])
        srv = StreamServer(pipe, fpca.DeltaGateConfig(threshold=0.05))
        srv.add_stream("s", list(configs))
        return list(srv.serve("s", frames)), srv

    fused, srv_f = serve(("a", "b"))
    assert srv_f.stats.fused_head_calls == len(frames)
    alone = {}
    for name in ("a", "b"):
        out, srv = serve((name,))
        assert srv.stats.fused_head_calls == 0
        alone[name] = out
    assert len(fused) == 2 * len(frames)
    for x in fused:
        y = alone[x.config][x.frame_idx]
        assert (x.config, x.frame_idx) == (y.config, y.frame_idx)
        np.testing.assert_array_equal(x.logits, y.logits)


# ---------------------------------------------------------------------------
# event streams
# ---------------------------------------------------------------------------


def test_event_stream_reconciles(bucket_model):
    _, model, _, _, pipe, server = _detect_setup(bucket_model)
    server.add_stream("cam", "det", events=True)
    frames = _frames(5, seed=13)
    results = list(server.serve("cam", frames))
    tap = server.event_taps["cam"]
    # one packet per tick, aligned; first tick has no delta -> empty packet
    assert [r.events.frame_idx for r in results] == list(range(5))
    assert results[0].events.n_events == 0
    assert results[2].events.n_events == 0    # quiet tick (repeated frame)
    assert tap.stats.ticks == 5
    total = sum(r.events.n_events for r in results)
    assert total == tap.stats.events > 0
    assert tap.stats.events == tap.stats.events_pos + tap.stats.events_neg
    st = server.sessions["cam"]._primary
    assert st.changed_total == tap.stats.events
    assert_reconciled(pipe, server)
    # raster round-trips coords and polarity
    p = next(r.events for r in results if r.events.n_events)
    grid = p.raster()
    assert grid.shape == p.grid_shape
    assert int(np.abs(grid).sum()) == p.n_events


def test_event_segment_matches_per_tick(bucket_model):
    frames = _frames(6, seed=17)
    _, _, _, _, pipe_a, srv_a = _detect_setup(bucket_model)
    srv_a.add_stream("cam", "det", events=True)
    list(srv_a.serve("cam", frames))
    want = [(p.frame_idx, p.coords.tolist(), p.polarity.tolist())
            for p in srv_a.event_taps["cam"].packets]

    # mixed serving: 3 per-tick, then one compiled segment for the rest
    _, _, _, _, pipe_b, srv_b = _detect_setup(bucket_model)
    srv_b.add_stream("cam", "det", events=True)
    list(srv_b.serve("cam", frames[:3]))
    seg_results = srv_b.run_segment("cam", frames[3:])
    got = [(p.frame_idx, p.coords.tolist(), p.polarity.tolist())
           for p in srv_b.event_taps["cam"].packets]
    assert got == want
    assert [r.events.frame_idx for r in seg_results] == [3, 4, 5]
    assert_reconciled(pipe_b, srv_b)


def test_event_tap_requires_gated_shared_gate(bucket_model):
    _, model, kernel, hp, pipe, _ = _detect_setup(bucket_model)
    dense = StreamServer(pipe, gating=False)
    with pytest.raises(ValueError, match="gated stream"):
        dense.add_stream("cam", "det", events=True)
    assert "cam" not in dense.sessions       # no half-attached stream
    per_cfg = StreamServer(pipe)
    with pytest.raises(NotImplementedError, match="per-config"):
        per_cfg.add_stream(
            "cam", ["det"], events=True,
            gate={"det": fpca.DeltaGateConfig(threshold=0.05)},
        )
    assert "cam" not in per_cfg.sessions


def test_fleet_report_breaks_out_workloads(bucket_model):
    _, model, _, _, pipe, server = _detect_setup(bucket_model)
    server.add_stream("cam", "det", events=True)
    # workload rows aggregate the process-global registry: diff against a
    # pre-serve snapshot so other tests' instances cancel out
    before = fleet_report(server)["workloads"]

    def row(wl, arch, name):
        return wl.get(arch, {}).get(name, 0)

    list(server.serve("cam", _frames(3, seed=19)))
    rep = fleet_report(server)
    wl = rep["workloads"]
    runs = row(wl, "fpca_detect", "fpca_model_runs_total") - row(
        before, "fpca_detect", "fpca_model_runs_total")
    ticks = row(wl, "events", "fpca_events_ticks") - row(
        before, "events", "fpca_events_ticks")
    assert runs > 0
    assert ticks == 3
    assert rep["fleet"]["fused_head_calls"] == 0
