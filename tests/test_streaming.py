"""Streaming subsystem: in-kernel region skipping, delta gate, serving loop.

Contracts pinned here:

* **Compute-real masking** — the window-compacted fused path (both the
  Pallas kernel in interpret mode and the XLA basis lowering) returns counts
  bit-identical to the dense reference on kept windows and exact zeros on
  skipped windows, across the reconfiguration grid (full sweep marked slow,
  a smoke subset in the fast lane).
* **Delta gate** — keyframes keep everything, static scenes go quiet,
  changed blocks stay live for exactly ``hysteresis`` extra frames.
* **Serving loop** — the double-buffered server yields results strictly in
  frame order regardless of depth, and multi-stream fan-in (one device batch
  for many cameras) matches looped single-stream serving bit-for-bit.
* **Cross-config batching** — configs sharing a compile signature merge into
  one channel-stacked call with unchanged per-request results.
* **Device-resident group state** — with the frames copied up once a tick
  and each group's previous effective frames and effective maps kept in
  one device state, gate decisions, counts, logits and event packets equal
  those of a reference that keeps every stream's state as host arrays,
  bit for bit.
"""

from __future__ import annotations

import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.core import analysis, gating
from repro.core.fpca_sim import fpca_forward
from repro.core.mapping import FPCASpec, active_window_mask, output_dims
from repro.data.pipeline import SyntheticMovingObject
from repro.fpca.program import ProgrammedModel
from repro.kernels.fpca_conv.ops import fpca_conv, window_bucket
from repro.serving.fpca_pipeline import FPCAPipeline, FrontendRequest
from repro.serving.saliency import saliency_mask
from repro.serving.streaming import (
    DeltaGateConfig,
    GateControllerConfig,
    StreamServer,
    StreamSession,
    _state_of,
    block_delta_mask,
)

H = W = 24


def _spec(kernel: int = 5, stride: int = 5, binning: int = 1) -> FPCASpec:
    return FPCASpec(
        image_h=H, image_w=W, out_channels=4, kernel=kernel, stride=stride,
        binning=binning,
    )


def _sparse_block_mask(spec: FPCASpec) -> np.ndarray:
    """Keep only the top-left block — actually exercises the gather path."""
    bh = -(-spec.eff_h // spec.skip_block)
    bw = -(-spec.eff_w // spec.skip_block)
    mask = np.zeros((bh, bw), bool)
    mask[0, 0] = True
    return mask


def _data(spec: FPCASpec, batch: int = 2, seed: int = 0):
    rng = np.random.default_rng(seed)
    images = rng.uniform(0, 1, (batch, H, W, spec.in_channels)).astype(np.float32)
    k = spec.kernel
    kernel = (rng.normal(size=(spec.out_channels, k, k, spec.in_channels)) * 0.2
              ).astype(np.float32)
    return images, kernel


def _assert_masked_parity(bucket_model, spec, backend, block_mask):
    images, kernel = _data(spec)
    common = dict(model=bucket_model, mode="bucket_sigmoid", hard=True)
    dense = np.asarray(
        fpca_forward(images, kernel, spec, **common)["counts"]
    )
    kw = {"interpret": True} if backend == "pallas" else {}
    got = np.asarray(
        fpca_forward(
            images, kernel, spec, backend=backend, block_mask=block_mask,
            **kw, **common,
        )["counts"]
    )
    keep = active_window_mask(spec, block_mask)
    np.testing.assert_array_equal(got[:, keep], dense[:, keep])
    assert np.all(got[:, ~keep] == 0)


# ---------------------------------------------------------------------------
# in-kernel region skipping: masked vs dense, bit-exact on kept windows
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["basis", "pallas"])
def test_masked_parity_smoke(bucket_model, backend):
    """Fast-lane streaming smoke: sparse mask through the compacted path."""
    spec = _spec(5, 5, 1)
    _assert_masked_parity(bucket_model, spec, backend, _sparse_block_mask(spec))


PARITY_GRID = [
    (kernel, stride, binning)
    for kernel in (3, 5)
    for stride in (kernel, 2)
    for binning in (1, 2)
]


@pytest.mark.slow
@pytest.mark.parametrize("kernel,stride,binning", PARITY_GRID)
@pytest.mark.parametrize("backend", ["basis", "pallas"])
def test_masked_parity_full_grid(bucket_model, kernel, stride, binning, backend):
    """Full reconfiguration grid x both fused backends (streaming sweep)."""
    spec = _spec(kernel, stride, binning)
    _assert_masked_parity(bucket_model, spec, backend, _sparse_block_mask(spec))


def test_window_bucket_bounded_pow2():
    assert window_bucket(1, 400) == 1
    assert window_bucket(3, 400) == 4
    assert window_bucket(129, 400) == 256
    assert window_bucket(300, 400) == 400   # capped -> dense fallback
    assert window_bucket(0, 400) == 1       # empty mask still a valid bucket


def test_pipeline_masked_request_skips_compute(bucket_model):
    """The scheduler executes only the kept-window bucket, not the grid."""
    spec = _spec()
    _, kernel = _data(spec)
    pipe = FPCAPipeline(bucket_model, backend="basis")
    pipe.register("cam", spec, kernel)
    h_o, w_o = output_dims(spec)
    mask = _sparse_block_mask(spec)
    img = _data(spec, batch=1)[0][0]
    out = pipe.submit([FrontendRequest("cam", img, block_mask=mask)])[0]
    keep = active_window_mask(spec, mask)
    dense = pipe.submit([FrontendRequest("cam", img)])[0]
    np.testing.assert_array_equal(np.asarray(out)[keep], np.asarray(dense)[keep])
    assert np.all(np.asarray(out)[~keep] == 0)
    # 2 batches: the masked one ran a pow2 bucket < full grid, the dense one
    # the whole grid
    assert pipe.stats.windows_executed < pipe.stats.windows_total
    assert pipe.stats.windows_executed < h_o * w_o + window_bucket(
        int(keep.sum()), h_o * w_o
    ) + 1


# ---------------------------------------------------------------------------
# temporal delta gate
# ---------------------------------------------------------------------------


def _flat_frames(spec, n, value=0.5):
    return [np.full((H, W, 3), value, np.float32) for _ in range(n)]


def test_block_delta_mask_localises_change():
    spec = _spec()
    a = np.full((spec.eff_h, spec.eff_w), 0.5, np.float32)
    b = a.copy()
    b[:8, 8:16] += 0.2                      # bump exactly block (0, 1)
    mask = block_delta_mask(a, b, spec, threshold=0.05)
    want = np.zeros_like(mask)
    want[0, 1] = True
    np.testing.assert_array_equal(mask, want)


def test_delta_gate_keyframe_and_hysteresis():
    spec = _spec()
    gate = DeltaGateConfig(threshold=0.05, hysteresis=1, keyframe_interval=6)
    from repro.serving.streaming import StreamSession

    session = StreamSession("s", "cam", spec, gate)
    frames = _flat_frames(spec, 10)
    # frame 2 changes one block, everything else is static
    frames[2] = frames[2].copy()
    frames[2][:8, :8] += 0.3
    masks = [session.step(f) for f in frames]
    assert masks[0].all()                   # first frame = keyframe
    assert not masks[1].any()               # static scene goes quiet
    assert masks[2][0, 0] and masks[2].sum() == 1       # change detected
    assert masks[3][0, 0] and masks[3].sum() == 1       # hysteresis frame 1
    # frame 4: change was 2 frames ago (> hysteresis) AND the bumped frame
    # reverting also registers as a change at frame 3 -> block lives one
    # extra pair, then dies
    assert masks[4][0, 0] and masks[4].sum() == 1       # revert delta + hyst
    assert not masks[5].any()
    assert masks[6].all()                   # keyframe refresh at interval 6
    assert not masks[7].any()               # ...and quiet again right after


def test_delta_gate_disabled_session_is_dense():
    from repro.serving.streaming import StreamSession

    session = StreamSession("s", "cam", _spec(), None)
    assert session.step(np.zeros((H, W, 3), np.float32)) is None


# ---------------------------------------------------------------------------
# double-buffered serving loop
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def stream_pipe(bucket_model):
    """One pipeline (and executable cache) shared by all serving-loop tests."""
    spec = _spec()
    _, kernel = _data(spec)
    pipe = FPCAPipeline(bucket_model, backend="basis")
    pipe.register("cam", spec, kernel)
    return pipe


def _make_server(pipe, n_streams=1, **server_kw):
    server = StreamServer(
        pipe, DeltaGateConfig(threshold=0.02, hysteresis=1, keyframe_interval=8),
        **server_kw,
    )
    for i in range(n_streams):
        server.add_stream(f"s{i}", "cam")
    return server


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_double_buffer_results_in_frame_order(stream_pipe, depth):
    """Results come back tick-ordered for any in-flight depth, and the depth
    never changes the numbers."""
    server = _make_server(stream_pipe, depth=depth)
    stream = SyntheticMovingObject((H, W), seed=3, radius=4.0)
    results = list(server.serve("s0", stream.frames(7)))
    assert [r.frame_idx for r in results] == list(range(7))
    ref_server = _make_server(stream_pipe, depth=1)
    ref = list(ref_server.serve("s0", stream.frames(7)))
    for a, b in zip(results, ref):
        np.testing.assert_array_equal(a.counts, b.counts)


def test_multi_stream_fan_in_matches_looped_single_stream(stream_pipe):
    """Two cameras in one device batch == each camera served alone."""
    server = _make_server(stream_pipe, n_streams=2, depth=2)
    cams = {
        "s0": SyntheticMovingObject((H, W), seed=4, radius=4.0),
        "s1": SyntheticMovingObject((H, W), seed=5, radius=4.0),
    }
    ticks = [{sid: cam.frame_at(t) for sid, cam in cams.items()} for t in range(5)]
    fanned = [r for results in server.run(ticks) for r in results]
    for sid, cam in cams.items():
        solo_server = _make_server(stream_pipe, depth=2)
        solo = list(solo_server.serve("s0", cam.frames(5)))
        mine = [r for r in fanned if r.stream_id == sid]
        assert [r.frame_idx for r in mine] == list(range(5))
        for a, b in zip(mine, solo):
            np.testing.assert_array_equal(a.counts, b.counts)
            np.testing.assert_array_equal(a.block_mask, b.block_mask)


def test_stream_server_gated_faster_windows_than_dense(stream_pipe):
    """The gate's executed-window count actually drops below dense."""
    server = _make_server(stream_pipe, depth=2)
    stream = SyntheticMovingObject((H, W), seed=6, radius=4.0)
    list(server.serve("s0", stream.frames(6)))
    assert server.stats.windows_kept < server.stats.windows_total
    assert stream_pipe.stats.windows_executed < stream_pipe.stats.windows_total
    rep = server.sessions["s0"].energy_report()
    assert rep["frames"] == 6
    assert 0 < rep["kept_window_frac"] < 1
    assert rep["energy_vs_dense"] < 1 and rep["latency_vs_dense"] <= 1


def test_stream_server_unknown_stream_or_config():
    from repro.core.curvefit import BucketCurvefitModel  # noqa: F401  (import path smoke)

    pipe = FPCAPipeline(backend="basis")
    server = StreamServer(pipe)
    with pytest.raises(KeyError):
        server.add_stream("s0", "nope")


# ---------------------------------------------------------------------------
# cross-config channel batching
# ---------------------------------------------------------------------------


def test_cross_config_batching_merges_and_matches(bucket_model):
    spec = _spec()
    rng = np.random.default_rng(11)
    kA = (rng.normal(size=(4, 5, 5, 3)) * 0.2).astype(np.float32)
    kB = (rng.normal(size=(4, 5, 5, 3)) * 0.2).astype(np.float32)
    img0 = rng.uniform(0, 1, (H, W, 3)).astype(np.float32)
    img1 = rng.uniform(0, 1, (H, W, 3)).astype(np.float32)
    reqs = [
        FrontendRequest("A", img0),
        FrontendRequest("B", img1),
        FrontendRequest("A", img1, block_mask=_sparse_block_mask(spec)),
    ]

    plain = FPCAPipeline(bucket_model, backend="basis")
    plain.register("A", spec, kA)
    plain.register("B", spec, kB)
    want = plain.submit(reqs)
    assert plain.stats.batches == 2 and plain.stats.merged_groups == 0

    merged = FPCAPipeline(bucket_model, backend="basis", cross_config_batching=True)
    merged.register("A", spec, kA)
    merged.register("B", spec, kB)
    got = merged.submit(reqs)
    assert merged.stats.batches == 1 and merged.stats.merged_groups == 1
    for a, b in zip(got, want):
        assert a.shape == (4, 4, 4)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cross_config_batching_leaves_distinct_specs_alone(bucket_model):
    specA, specB = _spec(5, 5, 1), _spec(3, 2, 1)
    rng = np.random.default_rng(12)
    pipe = FPCAPipeline(bucket_model, backend="basis", cross_config_batching=True)
    pipe.register("A", specA, (rng.normal(size=(4, 5, 5, 3)) * 0.2).astype(np.float32))
    pipe.register("B", specB, (rng.normal(size=(4, 3, 3, 3)) * 0.2).astype(np.float32))
    img = rng.uniform(0, 1, (H, W, 3)).astype(np.float32)
    res = pipe.submit([FrontendRequest("A", img), FrontendRequest("B", img)])
    assert pipe.stats.batches == 2 and pipe.stats.merged_groups == 0
    assert res[0].shape == (4, 4, 4)
    h_o, w_o = output_dims(specB)
    assert res[1].shape == (h_o, w_o, 4)


# ---------------------------------------------------------------------------
# saliency (library home of the former example helper)
# ---------------------------------------------------------------------------


def test_saliency_mask_shape_and_fraction():
    spec = _spec()
    rng = np.random.default_rng(13)
    img = rng.uniform(0, 1, (H, W, 3)).astype(np.float32)
    mask = saliency_mask(img, spec, keep_frac=0.4)
    bh = -(-spec.eff_h // spec.skip_block)
    bw = -(-spec.eff_w // spec.skip_block)
    assert mask.shape == (bh, bw) and mask.dtype == bool
    assert 1 <= mask.sum() <= mask.size


def test_saliency_mask_binned_grid():
    spec = _spec(5, 5, binning=2)
    rng = np.random.default_rng(14)
    img = rng.uniform(0, 1, (H, W, 3)).astype(np.float32)
    mask = saliency_mask(img, spec, keep_frac=0.5)
    bh = -(-spec.eff_h // spec.skip_block)
    bw = -(-spec.eff_w // spec.skip_block)
    assert mask.shape == (bh, bw)


# ---------------------------------------------------------------------------
# bucket-edge bitwise parity: the flap-prone kept counts the grid never pins
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["basis", "pallas"])
def test_masked_parity_at_bucket_edges(bucket_model, backend):
    """Bit-exact masked-vs-dense at n_keep = 0, 1, pow2-1, pow2, pow2+1, M.

    These kept counts sit exactly on the bucket boundaries (window_bucket
    transitions), where an off-by-one in the gather/row-validity logic would
    truncate a kept window or leak a padding row — the PR-2 parity grid only
    ever exercised one sparse mask far from the edges.
    """
    spec = _spec(5, 5, 1)
    images, kernel = _data(spec, batch=2)
    h_o, w_o = output_dims(spec)
    M = images.shape[0] * h_o * w_o
    dense = np.asarray(
        fpca_forward(
            images, kernel, spec, model=bucket_model, mode="bucket_sigmoid",
            hard=True,
        )["counts"]
    )
    kw = {"interpret": True} if backend == "pallas" else {}
    pow2 = 8
    rng = np.random.default_rng(21)
    scatter = rng.permutation(M)
    for n_keep in (0, 1, pow2 - 1, pow2, pow2 + 1, M):
        flat = np.zeros(M, bool)
        flat[scatter[:n_keep]] = True
        wm = flat.reshape(images.shape[0], h_o, w_o)
        got = np.asarray(
            fpca_conv(
                images, kernel, bucket_model, spec=spec, impl=backend,
                window_mask=wm, **kw,
            )
        )
        np.testing.assert_array_equal(got[wm], dense[wm], err_msg=f"n_keep={n_keep}")
        assert np.all(got[~wm] == 0), f"n_keep={n_keep}"


# ---------------------------------------------------------------------------
# zero-kept ticks: short-circuit, and the accounting stays division-safe
# ---------------------------------------------------------------------------


def test_masked_call_with_full_bucket_stays_trace_safe(bucket_model):
    """With an explicit full-size m_bucket the mask is never materialised on
    host, so the masked entry point still jits over traced masks (the
    zero-keep short-circuit must not regress this)."""
    import jax

    spec = _spec()
    images, kernel = _data(spec, batch=1)
    h_o, w_o = output_dims(spec)
    M = h_o * w_o

    @jax.jit
    def run(imgs, mask):
        return fpca_conv(
            imgs, kernel, bucket_model, spec=spec, impl="basis",
            window_mask=mask, m_bucket=M,
        )

    keep = np.zeros((1, h_o, w_o), bool)
    keep[0, 0, 0] = True
    out = np.asarray(run(images, keep))          # traces without concretising
    dense = np.asarray(
        fpca_forward(
            images, kernel, spec, model=bucket_model, mode="bucket_sigmoid",
            hard=True,
        )["counts"]
    )
    np.testing.assert_array_equal(out[keep], dense[keep])
    assert np.all(out[~keep] == 0)


@pytest.mark.parametrize("backend", ["basis", "pallas"])
def test_compacted_kernel_handles_zero_valid_rows(bucket_model, backend):
    """The in-kernel gather/row-validity path at zero valid rows.

    An eager all-false mask short-circuits on host before any launch, so
    this is only reachable through a pre-built bucketed executable (the
    serving cache's entry point, whose masks enter traced) — the kernel then
    runs a bucket whose every row is padding and the epilogue must still
    produce exact zeros."""
    import jax.numpy as jnp

    from repro.kernels.fpca_conv.ops import make_fpca_conv_executable

    spec = _spec()
    images, kernel = _data(spec, batch=1)
    h_o, w_o = output_dims(spec)
    kw = {"interpret": True} if backend == "pallas" else {}
    run_exe = make_fpca_conv_executable(
        bucket_model, spec=spec, impl=backend, m_bucket=8, **kw  # 8 < M
    )
    bn = jnp.zeros((spec.out_channels,), jnp.float32)

    def run(imgs, mask):
        return run_exe(jnp.asarray(imgs), jnp.asarray(kernel), bn, jnp.asarray(mask))

    out = np.asarray(run(images, np.zeros((1, h_o, w_o), bool)))
    assert out.shape == (1, h_o, w_o, spec.out_channels)
    assert np.all(out == 0)
    # ...and with valid rows present, the same jitted bucket stays bit-exact
    keep = np.zeros((1, h_o, w_o), bool)
    keep.flat[[0, 3, 7]] = True
    got = np.asarray(run(images, keep))
    dense = np.asarray(
        fpca_forward(
            images, kernel, spec, model=bucket_model, mode="bucket_sigmoid",
            hard=True,
        )["counts"]
    )
    np.testing.assert_array_equal(got[keep], dense[keep])
    assert np.all(got[~keep] == 0)


def test_zero_kept_tick_short_circuits_without_launch(bucket_model):
    spec = _spec()
    _, kernel = _data(spec)
    pipe = FPCAPipeline(bucket_model, backend="basis")
    pipe.register("cam", spec, kernel)
    h_o, w_o = output_dims(spec)
    img = _data(spec, batch=1)[0]
    before = (pipe.stats.batches, pipe.stats.windows_executed)
    out = pipe.run_config_batch("cam", img, np.zeros((1, h_o, w_o), bool))
    assert out.shape == (1, h_o, w_o, spec.out_channels)
    assert np.all(np.asarray(out) == 0)
    # no fused call was dispatched and no window was executed
    assert pipe.stats.batches == before[0]
    assert pipe.stats.windows_executed == before[1]
    assert pipe.stats.launches_skipped == 1


def test_zero_kept_accounting_no_division_by_zero():
    spec = _spec()
    bh = -(-spec.eff_h // spec.skip_block)
    bw = -(-spec.eff_w // spec.skip_block)
    empty = np.zeros((bh, bw), bool)
    lat = analysis.frontend_latency(spec, block_mask=empty)
    assert lat["n_cycles"] == 0 and lat["t_total"] == 0
    # zero work executed -> fps is the None sentinel (never Infinity: the
    # strict-JSON artifact writer rejects non-finite floats)
    assert lat["fps"] is None
    rep = analysis.streaming_frontend_report(spec, [empty, empty])
    assert rep["executed_windows"] == 0 and rep["executed_cycles"] == 0
    assert rep["kept_window_frac"] == 0 and rep["energy_vs_dense"] == 0
    assert rep["fps_effective"] is None
    json.dumps(rep, allow_nan=False)   # idle stream round-trips strict JSON
    # ...and through the session-level report
    session = StreamSession("s", "cam", spec, DeltaGateConfig())
    session.block_masks.extend([empty, empty])
    srep = session.energy_report()
    assert srep["executed_windows"] == 0
    assert srep["fps_effective"] is None


def test_all_skipped_stream_ticks_skip_launches(bucket_model):
    """A static scene (no keyframes) produces all-skipped ticks end to end."""
    spec = _spec()
    _, kernel = _data(spec)
    pipe = FPCAPipeline(bucket_model, backend="basis")
    pipe.register("cam", spec, kernel)
    server = StreamServer(
        pipe, DeltaGateConfig(threshold=0.05, hysteresis=0, keyframe_interval=0)
    )
    server.add_stream("s0", "cam")
    frame = np.full((H, W, 3), 0.5, np.float32)
    results = list(server.serve("s0", [frame] * 4))
    assert [r.kept_windows for r in results[1:]] == [0, 0, 0]
    assert all(np.all(r.counts == 0) for r in results[1:])
    assert server.stats.launches_skipped == 3


def test_serve_seconds_brackets_hand_timed_wall_clock(bucket_model):
    """``serve_seconds`` accumulates exactly the dispatch+finalize halves of
    each tick, so it is positive and never exceeds an enclosing hand-timed
    bracket; ``fps_wall`` derives from it (and is the None sentinel on a
    server that has never served)."""
    import time

    from repro.serving.observe import fleet_report

    spec = _spec()
    _, kernel = _data(spec)
    pipe = FPCAPipeline(bucket_model, backend="basis")
    pipe.register("cam", spec, kernel)
    server = StreamServer(pipe, DeltaGateConfig(threshold=0.05))
    server.add_stream("s0", "cam")
    assert server.stats.serve_seconds == 0
    assert fleet_report(server)["fleet"]["fps_wall"] is None
    frames = _data(spec, batch=6, seed=2)[0]
    t0 = time.perf_counter()
    results = list(server.serve("s0", frames))
    elapsed = time.perf_counter() - t0
    assert len(results) == 6
    assert 0 < server.stats.serve_seconds <= elapsed
    rep = fleet_report(server)["fleet"]
    assert rep["fps_wall"] == pytest.approx(6 / server.stats.serve_seconds)


def test_serve_seconds_billed_when_serving_raises(bucket_model):
    """The billing is single-exit (try/finally): a tick that raises
    mid-dispatch still accounts the wall time already spent, so fps_wall
    stays honest across failures."""
    spec = _spec()
    _, kernel = _data(spec)
    pipe = FPCAPipeline(bucket_model, backend="basis")
    pipe.register("cam", spec, kernel)
    server = StreamServer(pipe, DeltaGateConfig(threshold=0.05))
    server.add_stream("s0", "cam")
    good = _data(spec, batch=1, seed=3)[0][0]
    bad = np.zeros((7, 7, 3), np.float32)          # wrong sensor geometry
    with pytest.raises((ValueError, TypeError)):
        list(server.serve("s0", [good, bad]))
    assert server.stats.serve_seconds > 0
    # ...and segment mode bills through the same contract
    before = server.stats.serve_seconds
    with pytest.raises((ValueError, TypeError)):
        server.run_segment("s0", np.zeros((2, 7, 7, 3), np.float32))
    assert server.stats.serve_seconds > before


# ---------------------------------------------------------------------------
# sticky bucket hysteresis through the serving stack
# ---------------------------------------------------------------------------


def test_sticky_buckets_cut_switches_with_identical_outputs(bucket_model):
    """Keyframe-driven bucket flaps: patience rides them out, counts match."""
    spec = _spec()
    _, kernel = _data(spec)
    gate = DeltaGateConfig(threshold=0.02, hysteresis=1, keyframe_interval=4)
    stream = SyntheticMovingObject((H, W), seed=8, radius=4.0)

    def serve(patience):
        pipe = FPCAPipeline(bucket_model, backend="basis", bucket_patience=patience)
        pipe.register("cam", spec, kernel)
        server = StreamServer(pipe, gate)
        server.add_stream("s0", "cam")
        results = list(server.serve("s0", stream.frames(12)))
        return results, server

    flap, flap_server = serve(1)
    sticky, sticky_server = serve(8)
    # identical gate decisions, bit-identical activations
    for a, b in zip(flap, sticky):
        np.testing.assert_array_equal(a.counts, b.counts)
        np.testing.assert_array_equal(a.block_mask, b.block_mask)
    # keyframes force the dense bucket every 4 ticks: the stateless pipeline
    # flaps down after each, the sticky one holds
    assert flap_server.stats.bucket_switches > 0
    assert sticky_server.stats.bucket_switches < flap_server.stats.bucket_switches
    assert sticky_server.stats.bucket_shrinks_deferred > 0


# ---------------------------------------------------------------------------
# keep-fraction servo: convergence on the synthetic stream (§ acceptance)
# ---------------------------------------------------------------------------


def test_controller_converges_to_keep_budget():
    """The servo lands the kept fraction within ±20% of a 0.15 budget inside
    32 ticks of a SyntheticMovingObject stream (no kernels needed: the servo
    runs on the gate masks alone)."""
    spec = FPCASpec(image_h=64, image_w=64, out_channels=4, kernel=5, stride=5)
    from repro.serving.control import GateController

    gate = DeltaGateConfig(threshold=0.02, hysteresis=1, keyframe_interval=0)
    ctl = GateController(GateControllerConfig(target=0.15), spec, gate.threshold)
    session = StreamSession("s", "cam", spec, gate, controller=ctl)
    stream = SyntheticMovingObject((64, 64), seed=2, radius=7.0)
    for t in range(40):
        session.step(stream.frame_at(t))
    converged = ctl.converged_tick(rel_tol=0.2)
    assert converged is not None and converged <= 32
    assert 0.12 <= ctl.ema <= 0.18
    # the servoed threshold is what the session now gates with
    assert session.gate.threshold == ctl.threshold


def test_controller_server_wiring_per_stream(bucket_model):
    """Each stream servos independently; thresholds actually move."""
    spec = _spec()
    _, kernel = _data(spec)
    pipe = FPCAPipeline(bucket_model, backend="basis")
    pipe.register("cam", spec, kernel)
    server = StreamServer(
        pipe,
        DeltaGateConfig(threshold=0.02, hysteresis=1, keyframe_interval=0),
        controller=GateControllerConfig(target=0.3),
    )
    server.add_stream("s0", "cam")
    server.add_stream("s1", "cam")
    cams = {
        "s0": SyntheticMovingObject((H, W), seed=4, radius=4.0),
        "s1": SyntheticMovingObject((H, W), seed=9, radius=6.0),
    }
    ticks = [{sid: cam.frame_at(t) for sid, cam in cams.items()} for t in range(8)]
    for _ in server.run(ticks):
        pass
    c0 = server.sessions["s0"].controller
    c1 = server.sessions["s1"].controller
    assert c0 is not None and c1 is not None and c0 is not c1
    assert len(c0.history) == 8 and len(c1.history) == 8
    # different scenes -> different servoed thresholds
    assert server.sessions["s0"].gate.threshold != server.sessions["s1"].gate.threshold


# ---------------------------------------------------------------------------
# multi-config streams: one camera fanned to several programmed configs
# ---------------------------------------------------------------------------


def test_multi_config_stream_matches_single_config_serving(bucket_model):
    """One channel-stacked call per tick == each config served alone."""
    spec = _spec()
    rng = np.random.default_rng(31)
    kA = (rng.normal(size=(4, 5, 5, 3)) * 0.2).astype(np.float32)
    kB = (rng.normal(size=(6, 5, 5, 3)) * 0.2).astype(np.float32)
    gate = DeltaGateConfig(threshold=0.02, hysteresis=1, keyframe_interval=4)
    stream = SyntheticMovingObject((H, W), seed=12, radius=4.0)
    # ONE pipeline (and executable cache) serves all three runs: parity does
    # not depend on cache state, and sharing keeps the fast lane cheap
    pipe = FPCAPipeline(bucket_model, backend="basis")
    pipe.register("A", spec, kA)
    pipe.register("B", spec, kB)

    def serve(configs):
        server = StreamServer(pipe, gate)
        server.add_stream("s0", configs)
        return [
            r
            for results in server.run({"s0": stream.frame_at(t)} for t in range(5))
            for r in results
        ]

    b0, f0 = pipe.stats.batches, pipe.stats.fanout_batches
    fanned = serve(("A", "B"))
    # one result per (tick, config), served by ONE stacked call per tick
    assert pipe.stats.fanout_batches - f0 == 5
    assert pipe.stats.batches - b0 == 5         # not 10: the fan-out is fused
    soloA = serve("A")
    soloB = serve("B")
    assert [r.config for r in fanned] == ["A", "B"] * 5
    for got, want in zip([r for r in fanned if r.config == "A"], soloA):
        assert got.counts.shape == (4, 4, 4)
        np.testing.assert_array_equal(got.counts, want.counts)
        np.testing.assert_array_equal(got.block_mask, want.block_mask)
    for got, want in zip([r for r in fanned if r.config == "B"], soloB):
        assert got.counts.shape == (4, 4, 6)
        np.testing.assert_array_equal(got.counts, want.counts)


def test_per_config_gates_match_solo_serving(bucket_model):
    """add_stream(sid, ("A", "B"), gate={...}) gives each config its own
    gate state; every (stream, config) result is bit-identical to serving
    that config alone with that gate — even though the fused call executes
    only the union mask."""
    spec = _spec()
    rng = np.random.default_rng(41)
    kA = (rng.normal(size=(4, 5, 5, 3)) * 0.2).astype(np.float32)
    kB = (rng.normal(size=(6, 5, 5, 3)) * 0.2).astype(np.float32)
    gateA = DeltaGateConfig(threshold=0.01, hysteresis=1, keyframe_interval=4)
    gateB = DeltaGateConfig(threshold=0.08, hysteresis=0, keyframe_interval=0)
    stream = SyntheticMovingObject((H, W), seed=13, radius=4.0)
    pipe = FPCAPipeline(bucket_model, backend="basis")
    pipe.register("A", spec, kA)
    pipe.register("B", spec, kB)

    def serve(configs, gate):
        server = StreamServer(pipe)
        server.add_stream("s0", configs, gate=gate)
        return [
            r
            for results in server.run({"s0": stream.frame_at(t)} for t in range(6))
            for r in results
        ]

    fanned = serve(("A", "B"), {"A": gateA, "B": gateB})
    soloA = serve("A", gateA)
    soloB = serve("B", gateB)
    assert [r.config for r in fanned] == ["A", "B"] * 6
    for got, want in zip([r for r in fanned if r.config == "A"], soloA):
        assert got.kept_windows == want.kept_windows
        np.testing.assert_array_equal(got.block_mask, want.block_mask)
        np.testing.assert_array_equal(got.counts, want.counts)
    for got, want in zip([r for r in fanned if r.config == "B"], soloB):
        assert got.kept_windows == want.kept_windows
        np.testing.assert_array_equal(got.block_mask, want.block_mask)
        np.testing.assert_array_equal(got.counts, want.counts)
    # the tighter gate A and the looser gate B really made different calls
    keptA = [r.kept_windows for r in fanned if r.config == "A"]
    keptB = [r.kept_windows for r in fanned if r.config == "B"]
    assert keptA != keptB


def test_per_config_controllers_servo_independently(bucket_model):
    """One GateController per config of one camera: different budgets lead
    to different servoed thresholds within a single stream."""
    from repro.serving.streaming import GateControllerConfig as GCC

    spec = _spec()
    rng = np.random.default_rng(42)
    pipe = FPCAPipeline(bucket_model, backend="basis")
    pipe.register("A", spec, (rng.normal(size=(4, 5, 5, 3)) * 0.2).astype(np.float32))
    pipe.register("B", spec, (rng.normal(size=(4, 5, 5, 3)) * 0.2).astype(np.float32))
    gate = DeltaGateConfig(threshold=0.02, hysteresis=1, keyframe_interval=0)
    server = StreamServer(pipe)
    session = server.add_stream(
        "s0", ("A", "B"),
        gate={"A": gate, "B": gate},
        controller={"A": GCC(target=0.1), "B": GCC(target=0.5)},
    )
    cam = SyntheticMovingObject((H, W), seed=14, radius=5.0)
    for _ in server.run({"s0": cam.frame_at(t)} for t in range(8)):
        pass
    ctlA = session.state_for("A").controller
    ctlB = session.state_for("B").controller
    assert ctlA is not None and ctlB is not None and ctlA is not ctlB
    assert len(ctlA.history) == 8 and len(ctlB.history) == 8
    assert session.state_for("A").gate.threshold != session.state_for("B").gate.threshold
    # per-config energy accounting sees per-config histories
    repA = session.energy_report(config="A")
    repB = session.energy_report(config="B")
    assert repA["frames"] == repB["frames"] == 8
    assert repA["kept_window_frac"] != repB["kept_window_frac"]


def test_per_stream_gate_none_gives_dense_baseline(stream_pipe):
    """add_stream(gate=None) on a gated server disables gating for that
    stream only (omitting the argument inherits the server default)."""
    server = _make_server(stream_pipe, n_streams=1, depth=1)
    server.add_stream("dense", "cam", gate=None)
    stream = SyntheticMovingObject((H, W), seed=7, radius=4.0)
    ticks = [
        {"s0": stream.frame_at(t), "dense": stream.frame_at(t)}
        for t in range(4)
    ]
    results = [r for rs in server.run(ticks) for r in rs]
    dense = [r for r in results if r.stream_id == "dense"]
    gated = [r for r in results if r.stream_id == "s0"]
    h_o, w_o = output_dims(server.sessions["s0"].spec)
    assert all(r.block_mask is None and r.kept_windows == h_o * w_o for r in dense)
    assert any(r.kept_windows < h_o * w_o for r in gated[1:])


def test_per_config_gate_mapping_must_cover_all_configs(bucket_model):
    spec = _spec()
    rng = np.random.default_rng(43)
    pipe = FPCAPipeline(bucket_model, backend="basis")
    pipe.register("A", spec, (rng.normal(size=(4, 5, 5, 3)) * 0.2).astype(np.float32))
    pipe.register("B", spec, (rng.normal(size=(4, 5, 5, 3)) * 0.2).astype(np.float32))
    server = StreamServer(pipe)
    with pytest.raises(KeyError, match="missing config"):
        server.add_stream("s0", ("A", "B"), gate={"A": DeltaGateConfig()})


def test_multi_config_stream_requires_shared_spec(bucket_model):
    rng = np.random.default_rng(32)
    pipe = FPCAPipeline(bucket_model, backend="basis")
    pipe.register("A", _spec(5, 5, 1), (rng.normal(size=(4, 5, 5, 3)) * 0.2).astype(np.float32))
    pipe.register("B", _spec(3, 2, 1), (rng.normal(size=(4, 3, 3, 3)) * 0.2).astype(np.float32))
    server = StreamServer(pipe)
    with pytest.raises(ValueError, match="shared spec"):
        server.add_stream("s0", ("A", "B"))


# ---------------------------------------------------------------------------
# CompiledFrontend.stream() vs StreamServer: the single-camera loop serves
# the exact same ticks as solo server serving
# ---------------------------------------------------------------------------


def test_compiled_stream_matches_server_solo(bucket_model):
    """Tick-for-tick bit-identical parity between the handle's single-camera
    ``stream()`` loop and ``StreamServer`` solo serving of the same frames
    through the same gate (counts, masks, kept counts, frame order)."""
    import repro.fpca as fpca

    spec = _spec()
    _, kernel = _data(spec)
    gate = DeltaGateConfig(threshold=0.02, hysteresis=1, keyframe_interval=4)
    cam = SyntheticMovingObject((H, W), seed=9)
    frames = [cam.frame_at(t) for t in range(8)]

    pipe = FPCAPipeline(bucket_model, backend="basis")
    pipe.register("cam", spec, kernel)
    server = StreamServer(pipe, gate, depth=2)
    server.add_stream("s0", "cam")
    via_server = list(server.serve("s0", frames))

    fe = fpca.compile(
        fpca.FPCAProgram(spec=spec), backend="basis", weights=kernel,
        model=bucket_model,
    )
    via_handle = list(fe.stream(frames, gate=gate, depth=2))

    assert len(via_server) == len(via_handle) == len(frames)
    kept_some = False
    for s, h in zip(via_server, via_handle):
        assert s.frame_idx == h.frame_idx
        assert s.kept_windows == h.kept_windows
        assert s.total_windows == h.total_windows
        np.testing.assert_array_equal(s.block_mask, h.block_mask)
        np.testing.assert_array_equal(s.counts, h.counts)
        kept_some |= 0 < s.kept_windows < s.total_windows
    assert kept_some                        # the gate actually gated


def test_compiled_stream_matches_server_solo_dense(bucket_model):
    """Same parity with gating disabled (dense baseline both ways)."""
    import repro.fpca as fpca

    spec = _spec()
    _, kernel = _data(spec)
    rng = np.random.default_rng(11)
    frames = [rng.uniform(0, 1, (H, W, 3)).astype(np.float32) for _ in range(4)]

    pipe = FPCAPipeline(bucket_model, backend="basis")
    pipe.register("cam", spec, kernel)
    server = StreamServer(pipe, gating=False)
    server.add_stream("s0", "cam")
    via_server = list(server.serve("s0", frames))

    fe = fpca.compile(
        fpca.FPCAProgram(spec=spec), backend="basis", weights=kernel,
        model=bucket_model,
    )
    via_handle = list(fe.stream(frames, gate=None))
    for s, h in zip(via_server, via_handle):
        assert s.block_mask is None and h.block_mask is None
        assert s.kept_windows == h.kept_windows == s.total_windows
        np.testing.assert_array_equal(s.counts, h.counts)


# ---------------------------------------------------------------------------
# device-resident gate: the gate's pixels stay on the device
# ---------------------------------------------------------------------------


def _block_reduce_mean(x: np.ndarray, block: int) -> np.ndarray:
    """Mean over ``block x block`` tiles in numpy (ragged edge tiles average
    their real pixels only), shape ``(ceil(h/b), ceil(w/b))``."""
    h, w = x.shape
    bh, bw = -(-h // block), -(-w // block)
    padded = np.zeros((bh * block, bw * block), x.dtype)
    padded[:h, :w] = x
    sums = padded.reshape(bh, block, bw, block).sum((1, 3))
    ones = np.zeros((bh * block, bw * block), np.float32)
    ones[:h, :w] = 1.0
    counts = ones.reshape(bh, block, bw, block).sum((1, 3))
    return sums / counts


class _HostRoundTripServer(StreamServer):
    """The reference with every per-stream state on the host, one array per
    stream: its previous effective frame, and per model config its
    effective activation map.  The gate stacks the previous frames with the
    frames for one batched step (the solo kernel for a lone stream) and
    reads the new effective frames back; the event polarity is the numpy
    block mean of the host difference.  The head stacks the group's maps
    for one patched call and splits the result back into per-stream host
    arrays.  A segment starts from these arrays and leaves its carry in
    them."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.host_prev: dict[str, np.ndarray] = {}
        self.host_eff: dict[tuple[str, str], np.ndarray] = {}

    def _gate_batch(self, members, images, spec):
        kernels = gating.host_gate_kernels(spec)
        rows = [i for i, (s, _) in enumerate(members) if s.gating]
        ids = {i: members[i][0].stream_id for i in rows}
        prior = [i for i in rows if ids[i] in self.host_prev]
        if len(prior) > 1:
            curs, deltas = kernels.step_batch(
                np.stack([self.host_prev[ids[i]] for i in prior]),
                np.stack([members[i][1] for i in prior]),
            )
            pre = {i: (curs[j], deltas[j]) for j, i in enumerate(prior)}
        else:
            pre = {i: kernels.step(self.host_prev[ids[i]], members[i][1])
                   for i in prior}
        none = np.zeros(gating.block_grid(spec), np.float32)
        deltas, signed = [], []
        for i in rows:
            session, frame = members[i]
            if i not in pre:
                self.host_prev[ids[i]] = np.asarray(kernels.eff(frame))
                deltas.append(none)
                signed.append(none)
                continue
            cur, delta = (np.asarray(a) for a in pre[i])
            deltas.append(delta)
            signed.append(
                _block_reduce_mean(cur - self.host_prev[ids[i]], spec.skip_block)
                if session.want_events else none
            )
            self.host_prev[ids[i]] = cur
        events = any(members[i][0].want_events for i in rows)
        return (rows, np.stack(deltas), np.array([i in pre for i in rows]),
                np.stack(signed) if events else None)

    def _model_head_pass(self, launch, members, keep, h_o, w_o):
        counts = launch["counts"]
        logits = {}
        for name, lo, hi in launch["slices"]:
            cfg = self.pipeline._configs[name]
            if not isinstance(cfg, ProgrammedModel):
                continue
            zero = np.zeros((h_o, w_o, cfg.out_channels), np.float32)
            keys, windows = [], []
            for session, _ in members:
                keys.append((session.stream_id, name))
                st = session.state_for(name)
                windows.append(st.last_window_mask if session.gating
                               else np.ones((h_o, w_o), bool))
            # one call over the group's rows: a batch-1 head call is not
            # bit-equal to a batched one on every backend
            lg, eff = self.pipeline.model_handle_for(cfg.model).patched_logits(
                counts if lo is None else counts[..., lo:hi],
                np.stack([self.host_eff.get(k, zero) for k in keys]),
                np.stack(windows), head_params=cfg.head_params,
            )
            eff = np.asarray(eff)
            for row, k in enumerate(keys):
                self.host_eff[k] = eff[row]
            logits[name] = lg
        if logits:
            launch["logits"] = logits

    def _state_from_session(self, session, name):
        from repro.fpca.executable import SegmentState

        spec = session.spec
        prev = self.host_prev.get(session.stream_id)
        state = SegmentState(
            has_prev=prev is not None,
            prev_eff=(np.zeros((spec.eff_h, spec.eff_w), np.float32)
                      if prev is None else prev),
            age=session._primary.age, frame_idx=session.frame_idx,
        )
        cfg = self.pipeline._configs[name]
        if isinstance(cfg, ProgrammedModel):
            h_o, w_o = output_dims(spec)
            state.eff = self.host_eff.get(
                (session.stream_id, name),
                np.zeros((h_o, w_o, cfg.out_channels), np.float32),
            )
            state.logits = self.pipeline.model_handle_for(cfg.model).head_logits(
                state.eff[None], head_params=cfg.head_params)[0]
        return state

    def run_segment(self, stream_id, frames, **kwargs):
        out = super().run_segment(stream_id, frames, **kwargs)
        session = self.sessions[stream_id]
        carry = session._segment_state
        self.host_prev[stream_id] = np.asarray(carry.prev_eff, np.float32)
        if carry.eff is not None:
            self.host_eff[(stream_id, session.config)] = np.asarray(carry.eff)
        return out


def _model_program(spec):
    import repro.fpca as fpca

    return fpca.FPCAModelProgram(
        frontend=fpca.FPCAProgram(spec=spec),
        head=(fpca.DenseSpec(8, activation="relu"), fpca.DenseSpec(3)),
    )


@pytest.fixture(scope="module")
def resident_pipe(bucket_model):
    """A model config ``cls`` and two frontend configs ``A`` / ``B`` that
    share its spec (multi-config fan-out), on one executable cache."""
    spec = _spec()
    rng = np.random.default_rng(71)
    model = _model_program(spec)
    pipe = FPCAPipeline(bucket_model, backend="basis")
    pipe.register("cls", model,
                  (rng.normal(size=(4, 5, 5, 3)) * 0.2).astype(np.float32),
                  head_params=model.init_head(jax.random.PRNGKey(7)))
    pipe.register("A", spec, (rng.normal(size=(4, 5, 5, 3)) * 0.2).astype(np.float32))
    pipe.register("B", spec, (rng.normal(size=(6, 5, 5, 3)) * 0.2).astype(np.float32))
    return pipe


RESIDENT_GATE = DeltaGateConfig(threshold=0.02, hysteresis=1, keyframe_interval=5)


def _resident_scenario(name):
    """``(streams, steps)``: ``add_stream`` arguments, then runs of ticks
    (``("ticks", [present stream ids, ...])``, each stream's next frame of
    its own moving scene) and compiled segments (``("segment", sid, k)``)."""
    one = {}
    if name == "churn":            # streams join, leave, come back, reorder
        streams = [("c0", "cls", one), ("c1", "cls", one), ("c2", "cls", one)]
        steps = [("ticks", [("c0", "c1"), ("c0", "c1"), ("c0", "c1", "c2"),
                            ("c0", "c1", "c2"), ("c0", "c2"), ("c2", "c0"),
                            ("c2", "c0"), ("c0", "c1", "c2")])]
    elif name == "alone":          # one-stream groups
        streams = [("c0", "cls", one), ("c1", "A", one)]
        steps = [("ticks", [("c0", "c1")] * 6)]
    elif name == "per_config":     # per-config gates beside a shared gate
        gates = {"A": DeltaGateConfig(threshold=0.01, hysteresis=1,
                                      keyframe_interval=4),
                 "B": DeltaGateConfig(threshold=0.08, hysteresis=0,
                                      keyframe_interval=0)}
        streams = [("c0", ("A", "B"), {"gate": gates}), ("c1", ("A", "B"), one)]
        steps = [("ticks", [("c0", "c1")] * 7)]
    elif name == "events":         # an event tap in a batched group
        streams = [("c0", "cls", {"events": True}), ("c1", "cls", one)]
        steps = [("ticks", [("c0", "c1")] * 3 + [("c0",)] + [("c0", "c1")] * 3)]
    elif name == "interleave":     # per-tick -> segment -> per-tick
        streams = [("c0", "cls", {"events": True}), ("c1", "cls", one)]
        steps = [("ticks", [("c0", "c1")] * 3), ("segment", "c0", 4),
                 ("ticks", [("c0", "c1")] * 3), ("segment", "c0", 2),
                 ("ticks", [("c0", "c1")] * 2)]
    else:
        raise ValueError(name)
    return streams, steps


def _serve_resident(server_cls, pipe, name):
    streams, steps = _resident_scenario(name)
    server = server_cls(pipe, RESIDENT_GATE)
    cams = {}
    for i, (sid, configs, kw) in enumerate(streams):
        server.add_stream(sid, configs, **kw)
        cams[sid] = SyntheticMovingObject((H, W), seed=60 + i, radius=4.0)
    sent = {sid: 0 for sid in cams}

    def frame(sid):
        sent[sid] += 1
        return cams[sid].frame_at(sent[sid] - 1)

    out = []
    for step in steps:
        if step[0] == "ticks":
            ticks = [{sid: frame(sid) for sid in present} for present in step[1]]
            out += [r for results in server.run(ticks) for r in results]
        else:
            _, sid, k = step
            out += server.run_segment(sid, np.stack([frame(sid) for _ in range(k)]))
    return server, out


RESIDENT_SCENARIOS = ["churn", "alone", "per_config", "events", "interleave"]


@pytest.mark.parametrize("name", RESIDENT_SCENARIOS)
def test_device_resident_gate_matches_host_roundtrip(resident_pipe, name):
    """Gate decisions, window keep counts, counts, logits and event packets
    of a multi-tick fleet run equal the host-roundtrip gate's bit for bit,
    through stream churn, lone streams, per-config gates, event taps and
    the per-tick / segment interleave."""
    from repro.serving.observe import assert_reconciled

    server, got = _serve_resident(StreamServer, resident_pipe, name)
    _, want = _serve_resident(_HostRoundTripServer, resident_pipe, name)
    assert len(got) == len(want) > 0
    gated = False
    for a, b in zip(got, want):
        assert (a.stream_id, a.frame_idx, a.config) == (
            b.stream_id, b.frame_idx, b.config)
        assert a.kept_windows == b.kept_windows
        np.testing.assert_array_equal(a.block_mask, b.block_mask)
        np.testing.assert_array_equal(a.counts, b.counts)
        if b.logits is None:
            assert a.logits is None
        else:
            np.testing.assert_array_equal(a.logits, b.logits)
        if b.events is None:
            assert a.events is None
        else:
            assert a.events.coords.tolist() == b.events.coords.tolist()
            assert a.events.polarity.tolist() == b.events.polarity.tolist()
        gated |= 0 < a.kept_windows < a.total_windows
    assert gated                            # the gate actually gated
    s = server.stats
    assert s.gate_rows_resident > 0
    assert s.gate_rows_resident + s.gate_rows_restacked == s.frames - s.segment_ticks
    assert_reconciled(resident_pipe, server)
    if name in ("events", "interleave"):
        assert server.event_taps["c0"].stats.events > 0


def test_gate_row_counters_count_resident_and_restacked(resident_pipe):
    """Every gated stream-tick is either gated from the stack its group's
    last tick left on the device or restacked: a first frame, a stream
    joining or leaving, a reorder, a state a segment replaced."""
    server = StreamServer(resident_pipe, RESIDENT_GATE)
    for sid in ("c0", "c1", "c2"):
        server.add_stream(sid, "cls")
    cam = SyntheticMovingObject((H, W), seed=5, radius=4.0)
    frames = iter(cam.frame_at(t) for t in range(64))

    def run(*ticks):
        list(server.run([{sid: next(frames) for sid in t} for t in ticks]))
        return server.stats.gate_rows_resident, server.stats.gate_rows_restacked

    assert run(("c0", "c1")) == (0, 2)                    # first frames
    assert run(("c0", "c1"), ("c0", "c1")) == (4, 2)
    assert run(("c0", "c1", "c2")) == (4, 5)              # c2 joins
    assert run(("c0", "c1", "c2")) == (7, 5)
    assert run(("c0", "c2")) == (7, 7)                    # c1 leaves
    assert run(("c2", "c0")) == (7, 9)                    # reorder
    assert run(("c2", "c0")) == (9, 9)
    server.run_segment("c2", np.stack([next(frames) for _ in range(2)]))
    assert run(("c2", "c0")) == (9, 11)                   # segment replaced c2
    assert run(("c2", "c0")) == (11, 11)
    # a stream stepped alone holds a one-row state of its own
    session = StreamSession("solo", "cls", _spec(), RESIDENT_GATE,
                            stats=server.stats)
    for _ in range(3):
        session.step(next(frames))
        state, row = session._row
        assert state.sessions == [session] and row == 0
        assert state.prev.shape == (1, _spec().eff_h, _spec().eff_w)
    assert (server.stats.gate_rows_resident,
            server.stats.gate_rows_restacked) == (13, 12)


def test_steady_h2d_bytes_are_frame_plus_two_keep_grids(resident_pipe):
    """Once every row is resident, a frame costs its own bytes up plus the
    frontend's and the head's keep grids; only the |Δ| grid of the gate
    comes back beside the counts and logits."""
    spec = _spec()
    h_o, w_o = output_dims(spec)
    server = StreamServer(resident_pipe, RESIDENT_GATE)
    ids = ("c0", "c1", "c2", "c3")           # a pow-2 batch: no padding
    for sid in ids:
        server.add_stream(sid, "cls")
    rng = np.random.default_rng(3)
    ticks = [{sid: rng.uniform(0, 1, (H, W, 3)).astype(np.float32) for sid in ids}
             for _ in range(5)]
    list(server.run(ticks[:2]))
    s = server.stats
    h2d, d2h, frames = s.h2d_bytes, s.d2h_bytes, s.frames
    resident = s.gate_rows_resident
    list(server.run(ticks[2:]))
    n = s.frames - frames
    assert s.gate_rows_resident - resident == n == 3 * len(ids)
    frame, keep = 4 * H * W * 3, h_o * w_o
    grid = 4 * int(np.prod(gating.block_grid(spec)))
    assert (s.h2d_bytes - h2d) / n == frame + 2 * keep
    assert (s.d2h_bytes - d2h) / n == grid + 4 * h_o * w_o * 4 + 4 * 3


def test_streams_left_behind_keep_their_own_row(resident_pipe):
    """A stream that sits out a tick holds a one-row state of its own, with
    its previous frame and its effective map, not the state its group left
    behind, and resumes from it bit-identically."""
    ids = ("c0", "c1", "c2")
    cam = SyntheticMovingObject((H, W), seed=8, radius=4.0)
    frames = [cam.frame_at(t) for t in range(5)]
    ticks = [{sid: frames[t] for sid in ids} for t in range(2)]
    ticks += [{sid: frames[2] for sid in ("c0", "c2")}]
    back = [{sid: frames[t] for sid in ids} for t in (3, 4)]

    server = StreamServer(resident_pipe, RESIDENT_GATE)
    for sid in ids:
        server.add_stream(sid, "cls")
    list(server.run(ticks[:2]))
    c1 = server.sessions["c1"]
    group, j = c1._row
    prev, eff = c1._prev, np.asarray(group.eff["cls"][j])
    list(server.run(ticks[2:]))
    state, row = c1._row
    assert state.sessions == [c1] and row == 0
    assert state.prev.shape == (1, _spec().eff_h, _spec().eff_w)
    np.testing.assert_array_equal(c1._prev, prev)
    np.testing.assert_array_equal(np.asarray(state.eff["cls"][0]), eff)
    group = server.sessions["c0"]._row[0]
    assert [s.stream_id for s in group.sessions] == ["c0", "c2"]
    assert server.sessions["c2"]._row == (group, 1)
    resumed = [r for rs in server.run(back) for r in rs]

    # the host-state reference served the same ticks
    ref = _HostRoundTripServer(resident_pipe, RESIDENT_GATE)
    for sid in ids:
        ref.add_stream(sid, "cls")
    list(ref.run(ticks))
    want = [r for rs in ref.run(back) for r in rs]
    assert len(resumed) == len(want) == 6
    for a, b in zip(resumed, want):
        assert (a.stream_id, a.frame_idx) == (b.stream_id, b.frame_idx)
        assert a.kept_windows == b.kept_windows
        np.testing.assert_array_equal(a.block_mask, b.block_mask)
        np.testing.assert_array_equal(a.counts, b.counts)
        np.testing.assert_array_equal(a.logits, b.logits)


# ---------------------------------------------------------------------------
# the group gate pass: one host pass over a group's gate states
# ---------------------------------------------------------------------------


def _reference_window_mask(spec, keep):
    """The window keep grid pixel by pixel: a window executes iff any pixel
    of its (clipped) footprint lies in a kept block."""
    h_o, w_o = output_dims(spec)
    b, n, s = spec.skip_block, spec.max_kernel, spec.stride
    pixel = np.kron(keep, np.ones((b, b), bool))[: spec.eff_h, : spec.eff_w]
    return np.array([[pixel[r * s:r * s + n, c * s:c * s + n].any()
                      for c in range(w_o)] for r in range(h_o)], bool).reshape(h_o, w_o)


def _reference_gate_step(st, spec, delta, frame_idx):
    """One gate state advanced alone by one frame (``delta`` None on the
    first frame): the per-stream gate step the group pass replaced."""
    if delta is not None:
        changed = delta > np.float32(st.gate.threshold)
        st.age = np.where(changed, 0, st.age + 1)
        st.last_changed = changed
        st.changed_total += int(changed.sum())
    else:
        st.last_changed = None
    keyframe = delta is None or (
        st.gate.keyframe_interval > 0
        and frame_idx % st.gate.keyframe_interval == 0
    )
    keep = (np.ones_like(st.age, bool) if keyframe
            else st.age <= st.gate.hysteresis)
    st.last_keyframe = keyframe
    st.last_block_mask = keep
    st.block_masks.append(keep)
    window = _reference_window_mask(spec, keep)
    st.last_window_mask = window
    if st.controller is not None:
        obs = (float(window.mean())
               if st.controller.config.metric == "keep" else None)
        new_thr = st.controller.observe(keep, keyframe=keyframe, observation=obs)
        if new_thr != st.gate.threshold:
            st.gate = dataclasses.replace(st.gate, threshold=new_thr)
    return keep


class _PerStreamGateServer(StreamServer):
    """The reference server: the same batched gate dispatch, then each
    stream's gate states stepped one by one and the keep grids stacked."""

    def _gate_group(self, members, images, spec, h_o, w_o, gated):
        pre = {}
        _state_of([session for session, _ in members], self.stats)
        if gated:
            rows, deltas, has, signed = self._gate_batch(members, images, spec)
            pre = {i: (deltas[j] if has[j] else None,
                       signed[j] if signed is not None and has[j] else None)
                   for j, i in enumerate(rows)}
        entries, keeps = [], []
        for row, (session, _) in enumerate(members):
            frame_idx = session.frame_idx
            block = window = None
            if session.gating:
                delta, sgn = pre[row]
                if session.want_events:
                    session._last_signed = sgn
                for st in session._states:
                    keep = _reference_gate_step(st, spec, delta, frame_idx)
                    block = keep if block is None else block | keep
                    window = (st.last_window_mask if window is None
                              else window | st.last_window_mask)
                session.last_window_mask = window
            session.frame_idx += 1
            kept = int(window.sum()) if window is not None else h_o * w_o
            entry = {"stream_id": session.stream_id, "frame_idx": frame_idx,
                     "block_mask": block, "kept": kept, "total": h_o * w_o}
            if session.per_config:
                entry["per_config"] = {
                    st.name: (st.last_block_mask, int(st.last_window_mask.sum()),
                              st.last_window_mask)
                    for st in session._states}
            tap = self.event_taps.get(session.stream_id)
            if tap is not None:
                entry["events"] = tap.observe_tick(frame_idx)
            entries.append(entry)
            if gated:
                keeps.append(window if window is not None
                             else np.ones((h_o, w_o), bool))
            self.stats.frames += 1
            self.stats.windows_total += h_o * w_o
            self.stats.windows_kept += kept
        return entries, np.stack(keeps) if gated else None


GROUP_GATE = DeltaGateConfig(threshold=0.02, hysteresis=1, keyframe_interval=3)
_KEEP = GateControllerConfig(target=0.3, metric="keep")
_ENERGY = GateControllerConfig(target=0.4, metric="energy")


def _group_pass_scenario(name):
    """``(fleet, steps)``: whether a FleetController drives the server, and
    ``("add", sid, configs, kwargs)`` joins, ``("tick", sids)`` ticks (each
    stream's next frame of its own moving scene) and ``("segment", sid, k)``
    compiled segments."""
    one = {}
    per_config = {"gate": {
        "A": DeltaGateConfig(threshold=0.01, hysteresis=1, keyframe_interval=4),
        "B": DeltaGateConfig(threshold=0.08, hysteresis=0, keyframe_interval=0)}}
    if name == "churn":        # first frames, joins, leaves, keyframe ticks
        return False, [
            ("add", "c0", "cls", one), ("add", "c1", "cls", one),
            ("tick", ("c0", "c1")), ("tick", ("c0", "c1")),
            ("add", "c2", "cls", one), ("tick", ("c0", "c1", "c2")),
            ("tick", ("c0", "c2")), ("tick", ("c2", "c0")),
            ("tick", ("c0", "c1", "c2")), ("tick", ("c0", "c1", "c2"))]
    if name == "per_config":   # per-config thresholds beside shared and dense
        return False, [
            ("add", "c0", ("A", "B"), per_config), ("add", "c1", ("A", "B"), one),
            ("add", "c2", ("A", "B"), {"gate": None}),
            *[("tick", ("c0", "c1", "c2"))] * 6]
    if name == "servo":        # keep and energy servos under a fleet retarget
        return True, [
            ("add", "c0", "cls", {"controller": _KEEP}),
            ("add", "c1", "cls", {"controller": _ENERGY}),
            ("add", "c2", ("A", "B"), {**per_config, "controller": {
                "A": _KEEP, "B": _ENERGY}}),
            *[("tick", ("c0", "c1", "c2"))] * 7]
    if name == "events":       # an event tap in a batched group
        return False, [
            ("add", "c0", "cls", {"events": True}), ("add", "c1", "cls", one),
            *[("tick", ("c0", "c1"))] * 3, ("tick", ("c0",)),
            *[("tick", ("c0", "c1"))] * 2]
    if name == "interleave":   # per-tick -> segment -> per-tick
        return False, [
            ("add", "c0", "cls", {"events": True}), ("add", "c1", "cls", one),
            *[("tick", ("c0", "c1"))] * 3, ("segment", "c0", 3),
            *[("tick", ("c0", "c1"))] * 3]
    raise ValueError(name)


def _gate_snapshot(server):
    """Every gate state's host decision state, copied."""
    snap = {}
    for sid, session in server.sessions.items():
        win = session.last_window_mask
        snap[sid] = [session.frame_idx, None if win is None else win.copy()]
        for st in session._states:
            snap[sid].append((
                st.name, st.age.copy(), st.changed_total, st.last_keyframe,
                st.gate, None if st.last_changed is None else st.last_changed.copy(),
                st.last_block_mask.copy(), st.last_window_mask.copy(),
                [m.copy() for m in st.block_masks],
                None if st.controller is None else (
                    st.controller.threshold, st.controller.ema,
                    st.controller.config.target),
            ))
    return snap


def _serve_group_pass(server_cls, pipe, name):
    from repro.serving.fleet import FleetConfig, FleetController

    use_fleet, steps = _group_pass_scenario(name)
    server = server_cls(pipe, GROUP_GATE)
    fleet = FleetController(server, FleetConfig(rebalance_ticks=2)) if use_fleet else None
    cams, sent, out, snaps = {}, {}, [], []

    def frame(sid):
        sent[sid] += 1
        return cams[sid].frame_at(sent[sid] - 1)

    for step in steps:
        if step[0] == "add":
            _, sid, configs, kw = step
            (fleet or server).add_stream(sid, configs, **kw)
            cams[sid] = SyntheticMovingObject((H, W), seed=80 + len(cams), radius=4.0)
            sent[sid] = 0
            continue
        if step[0] == "tick":
            out += [r for results in (fleet or server).run([{sid: frame(sid) for sid in step[1]}])
                    for r in results]
        else:
            _, sid, k = step
            out += server.run_segment(sid, np.stack([frame(sid) for _ in range(k)]))
        snaps.append(_gate_snapshot(server))
    allocations = None if fleet is None else (
        fleet.rebalances, {m.stream_id: m.allocation for m in fleet._members.values()})
    return server, out, snaps, allocations


def _assert_same(a, b, where):
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        assert isinstance(a, np.ndarray) and isinstance(b, np.ndarray), where
        assert a.dtype == b.dtype, where
        np.testing.assert_array_equal(a, b, err_msg=where)
    elif isinstance(a, (list, tuple)):
        assert type(a) is type(b) and len(a) == len(b), where
        for i, (x, y) in enumerate(zip(a, b)):
            _assert_same(x, y, f"{where}[{i}]")
    else:
        assert a == b, where


@pytest.mark.parametrize("name", ["churn", "per_config", "servo", "events", "interleave"])
def test_group_gate_pass_matches_per_stream_steps(resident_pipe, name):
    """The one host pass over a group's gate states leaves every state as
    the per-stream step would, tick for tick: ages, keep and window grids,
    changed counts, mask history, keyframes, thresholds and servos; and
    the results, event packets and fleet allocations are the same."""
    from repro.serving.observe import assert_reconciled

    server, got, got_snaps, got_alloc = _serve_group_pass(StreamServer, resident_pipe, name)
    _, want, want_snaps, want_alloc = _serve_group_pass(
        _PerStreamGateServer, resident_pipe, name)
    assert len(got_snaps) == len(want_snaps)
    for t, (a, b) in enumerate(zip(got_snaps, want_snaps)):
        assert a.keys() == b.keys()
        for sid in a:
            _assert_same(a[sid], b[sid], f"tick {t} stream {sid}")
    assert len(got) == len(want) > 0
    for a, b in zip(got, want):
        for field in ("stream_id", "frame_idx", "config", "kept_windows",
                      "total_windows", "block_mask", "counts", "logits"):
            _assert_same(getattr(a, field), getattr(b, field), field)
        assert (a.events is None) == (b.events is None)
        if a.events is not None:
            assert a.events.coords.tolist() == b.events.coords.tolist()
            assert a.events.polarity.tolist() == b.events.polarity.tolist()
    assert got_alloc == want_alloc
    assert any(0 < r.kept_windows < r.total_windows for r in got)
    # a keyframe tick past a stream's first frame
    assert any(state[3] for snap in got_snaps for row in snap.values()
               if row[0] > 1 for state in row[2:])
    assert_reconciled(resident_pipe, server)
    if name == "servo":
        assert got_alloc[0] > 0
        assert any(st.gate.threshold != GROUP_GATE.threshold
                   for s in server.sessions.values() for st in s._states)
    if name in ("events", "interleave"):
        assert server.event_taps["c0"].stats.events > 0
