"""Grouped (depthwise) convolution, ``relu6`` and the global average pool in
the digital head, and P2M's MobileNetV2 VWW network built on them
(``zoo.build_model({"arch": "fpca_mobilenetv2"})``).

Contracts pinned here:

* a grouped ``ConvSpec`` equals one ungrouped conv per channel group, in
  chain and graph heads alike; ``relu6`` clips to ``[0, 6]``;
* group divisibility and weight shapes fail with the stage named;
* ``groups == 1`` leaves every signature byte-identical (golden pins);
* the zoo's ``fpca_mobilenetv2`` at published widths equals the plain
  Table-2 reference (``tests/mobilenetv2_ref.py``), and serves through
  ``StreamServer`` and ``run_segment``;
* ``analysis.head_flops`` counts its MACs exactly;
* ``precision="int8"`` refuses a grouped conv, naming the node.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.fpca as fpca
from repro.core import analysis
from repro.core.mapping import FPCASpec, active_window_mask
from repro.fpca import zoo
from repro.fpca.program import ConvSpec, DenseSpec
from repro.models import heads
from repro.models.layers import conv2d
from repro.serving.fpca_pipeline import FPCAPipeline
from repro.serving.streaming import StreamServer

import mobilenetv2_ref as ref

pytestmark = pytest.mark.zoo

# A 40x40 frame leaves an 8x8x8 count map, the size the benchmark's
# self-test serves; the network's widths are the published ones.
H = W = 40
# Float32 on the CPU: the program's convolutions and the reference's taps
# and einsums sum the same products in other orders over ~50 layers, so
# they agree to rounding, not to the bit.
RTOL = 1e-4


def _spec(h: int = H, w: int = W) -> FPCASpec:
    return FPCASpec(image_h=h, image_w=w, out_channels=8, kernel=5, stride=5)


def _kernel(spec: FPCASpec, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    k = spec.kernel
    return (rng.normal(size=(spec.out_channels, k, k, spec.in_channels))
            * 0.2).astype(np.float32)


def _mbv2(spec: FPCASpec | None = None, input_scale: float = 1 / 16):
    return zoo.build_model({"arch": "fpca_mobilenetv2", "spec": spec or _spec(),
                            "input_scale": input_scale})


def _counts(shape, seed: int) -> np.ndarray:
    """Count maps in the range the in-pixel layer gives on camera frames."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, 33, shape).astype(np.float32)


# ---------------------------------------------------------------------------
# grouped conv and relu6
# ---------------------------------------------------------------------------


def _per_group(p, x, groups, stride, padding):
    """One ungrouped conv per channel group, concatenated."""
    c_in, c_out = x.shape[-1], p["w"].shape[0]
    gi, go = c_in // groups, c_out // groups
    outs = [conv2d({"w": p["w"][g * go:(g + 1) * go], "b": p["b"][g * go:(g + 1) * go]},
                   x[..., g * gi:(g + 1) * gi], stride, padding)
            for g in range(groups)]
    return jnp.concatenate(outs, axis=-1)


@pytest.mark.parametrize("c_in, c_out, groups, stride", [
    (6, 6, 6, 1),        # depthwise
    (6, 6, 6, 2),        # depthwise, strided
    (6, 12, 6, 1),       # depthwise with a channel multiplier
    (6, 4, 2, 1),        # two groups
])
def test_grouped_conv_matches_per_group_loop(c_in, c_out, groups, stride):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 9, c_in))
    p = {"w": jax.random.normal(jax.random.PRNGKey(1), (c_out, 3, 3, c_in // groups)),
         "b": jax.random.normal(jax.random.PRNGKey(2), (c_out,))}
    got = conv2d(p, x, stride, "SAME", groups)
    want = _per_group(p, x, groups, stride, "SAME")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("graph", [False, True], ids=["chain", "graph"])
def test_grouped_conv_head_matches_per_group_loop(graph, bucket_model):
    """A depthwise ``relu6`` conv stage of a model head, chain or graph,
    against the per-channel loop; ``relu6`` clips to [0, 6]."""
    spec = FPCASpec(image_h=20, image_w=20, out_channels=4, kernel=5, stride=5)
    dw = ConvSpec(4, 3, padding="SAME", activation="relu6", groups=4)
    if graph:
        head = heads.HeadGraph(nodes=(heads.Node("dw", dw, ("input",)),
                                      heads.Node("fc", DenseSpec(2), ("dw",))),
                               output="fc")
    else:
        head = (dw, DenseSpec(2))
    model = fpca.FPCAModelProgram(frontend=fpca.FPCAProgram(spec=spec), head=head)
    params = model.init_head(jax.random.PRNGKey(3))
    p_dw = params["dw"] if graph else params[0]
    p_fc = params["fc"] if graph else params[1]
    assert p_dw["w"].shape == (4, 3, 3, 1)
    counts = jnp.asarray(_counts((2, 4, 4, 4), 0))
    x = jnp.clip(_per_group(p_dw, counts, 4, 1, "SAME"), 0.0, 6.0)
    want = x.reshape(2, -1) @ p_fc["w"] + p_fc["b"]
    got = model.apply_head(params, counts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jax.nn.relu6(counts))) == 6.0


def test_conv_groups_errors():
    with pytest.raises(ValueError, match="groups 3 do not divide out_channels 4"):
        ConvSpec(4, 3, groups=3)
    with pytest.raises(ValueError, match="groups must be >= 1"):
        ConvSpec(4, 3, groups=0)
    spec = FPCASpec(image_h=20, image_w=20, out_channels=3, kernel=5, stride=5)
    fe = fpca.FPCAProgram(spec=spec)
    bad = ConvSpec(4, 3, padding="SAME", groups=2)
    with pytest.raises(ValueError, match=r"node 'dw': conv groups 2 do not divide "
                                         r"input channels 3"):
        fpca.FPCAModelProgram(frontend=fe, head=heads.HeadGraph(
            nodes=(heads.Node("dw", bad), heads.Node("fc", DenseSpec(2), ("dw",))),
            output="fc"))
    with pytest.raises(ValueError, match=r"head\[0\]: conv groups 2 do not divide "
                                         r"input channels 3"):
        fpca.FPCAModelProgram(frontend=fe, head=(bad, DenseSpec(2)))
    model = _mbv2()
    hp = model.init_head(jax.random.PRNGKey(0))
    hp["block_1_depthwise"] = {"w": jnp.zeros((96, 3, 3, 96)), "b": jnp.zeros((96,))}
    with pytest.raises(ValueError, match=r"head node 'block_1_depthwise'.*"
                                         r"\(96, 3, 3, 1\)"):
        model.bind_head_params(hp)


# golden pins of the graph archs, as they read before convs had groups
GOLDEN_GRAPH_HEADS = {
    "fpca_resnet": (
        "head_graph", "repro.fpca.head_graph/1",
        ("node", "stem", ("input",), ("conv", 16, 3, 1, "SAME", "relu")),
        ("node", "conv1", ("stem",), ("conv", 16, 3, 1, "SAME", "relu")),
        ("node", "conv2", ("conv1",), ("conv", 16, 3, 1, "SAME", "")),
        ("node", "join", ("stem", "conv2"), ("add", "relu")),
        ("node", "pool", ("join",), ("pool", "avg", 2, 2)),
        ("node", "fc", ("pool",), ("dense", 32, "relu")),
        ("node", "logits", ("fc",), ("dense", 2, "")),
        ("output", "logits"),
    ),
    "fpca_detect": (
        "head_graph", "repro.fpca.head_graph/1",
        ("node", "trunk", ("input",), ("conv", 16, 3, 1, "SAME", "relu")),
        ("node", "det", ("trunk",), ("detect", 2, 1)),
        ("output", "det"),
    ),
}


@pytest.mark.parametrize("arch", sorted(GOLDEN_GRAPH_HEADS))
def test_ungrouped_signatures_unchanged(arch):
    spec = FPCASpec(image_h=20, image_w=20, out_channels=3, kernel=5, stride=5)
    sig = zoo.build_model({"arch": arch, "spec": spec}).signature()
    assert sig[-2] == GOLDEN_GRAPH_HEADS[arch]
    assert ConvSpec(8, 3)._sig() == ConvSpec(8, 3, groups=1)._sig() == (
        "conv", 8, 3, 1, "VALID", "relu")
    assert ConvSpec(8, 3, groups=8)._sig()[-1] == ("groups", 8)


# ---------------------------------------------------------------------------
# fpca_mobilenetv2
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("h, w, macs", [(560, 560, 281_132_416), (40, 40, 2_642_944)])
def test_mobilenetv2_head_flops_exact(h, w, macs):
    model = _mbv2(_spec(h, w))
    fl = analysis.head_flops(model)
    assert fl["macs"] == macs
    assert fl["flops"] == 2 * macs
    assert fl["params"] == 2_207_858
    depthwise = [r for r in fl["per_layer"] if "_depthwise:" in r["layer"]]
    assert len(depthwise) == 17


def test_mobilenetv2_structure():
    model = _mbv2(_spec(560, 560))
    shapes = model.head.shapes(model.frontend.out_shape)
    assert model.frontend.out_shape == (112, 112, 8)
    assert sum(n.name.endswith("_add") for n in model.head.nodes) == 10
    assert sum(n.name.endswith("_project") for n in model.head.nodes) == 17
    assert shapes["block_2_expand"] == (56, 56, 144)
    assert shapes["block_1_expand"] == (112, 112, 96)     # the largest map
    assert shapes["conv_1"] == (7, 7, 1280) and shapes["pool"] == (1280,)
    assert model.head_out_shape == (2,) and model.n_classes == 2
    # whatever the frame leaves, the pool covers all of it
    odd = _mbv2(_spec(40, 80)).head.shapes((8, 16, 8))
    assert odd["conv_1"] == (1, 1, 1280) and odd["pool"] == (1280,)
    assert set(model.init_head(jax.random.PRNGKey(0))) == set(
        ref.param_shapes(8, 2))


@pytest.mark.parametrize("h, w", [(40, 40), (40, 80)])
def test_mobilenetv2_matches_plain_reference(h, w):
    model = _mbv2(_spec(h, w))
    params = ref.make_params(jax.random.PRNGKey(5), 8)
    counts = _counts((3,) + model.frontend.out_shape, 1)
    got = np.asarray(model.apply_head(model.bind_head_params(params), counts))
    want = np.asarray(ref.forward(params, jnp.asarray(counts), 1 / 16))
    assert got.shape == (3, 2)
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=RTOL * np.abs(want).max())
    # the logits answer to the input: a different map moves them
    other = np.asarray(ref.forward(params, jnp.asarray(_counts(counts.shape, 2)), 1 / 16))
    assert np.abs(other - want).max() > 100 * RTOL * np.abs(want).max()


def _moving_frames(n: int, seed: int = 7) -> np.ndarray:
    rng = np.random.default_rng(seed)
    base = rng.uniform(0, 1, (H, W, 3)).astype(np.float32)
    frames = np.stack([base] * n)
    for t in range(1, n):
        frames[t, :16, :16] = rng.uniform(0, 1, (16, 16, 3))
    return frames


def _served(bucket_model, params, kernel):
    pipe = FPCAPipeline(bucket_model, backend="basis")
    pipe.register("mbv2", _mbv2(), kernel, head_params=params)
    server = StreamServer(pipe, fpca.DeltaGateConfig(threshold=0.02, hysteresis=0,
                                                     keyframe_interval=0))
    server.add_stream("cam", "mbv2")
    return server


def test_mobilenetv2_serves_through_stream_server_and_segment(bucket_model):
    """Gated per-tick serving: each tick's logits equal the reference on
    the effective map its served counts build; a device-compiled segment of
    the same frames matches per tick (to float32 rounding: the scan and the
    per-tick executable lower the head separately)."""
    spec = _spec()
    kernel = _kernel(spec)
    params = ref.make_params(jax.random.PRNGKey(6), 8)
    frames = _moving_frames(5)
    per_tick = list(_served(bucket_model, params, kernel).serve("cam", frames))
    assert any(0 < r.kept_windows < r.total_windows for r in per_tick)
    eff = np.zeros((8, 8, 8), np.float32)
    for r in per_tick:
        window = (np.ones((8, 8), bool) if r.block_mask is None
                  else active_window_mask(spec, r.block_mask))
        eff = np.where(window[..., None], r.counts, eff)
        want = np.asarray(ref.forward(params, jnp.asarray(eff[None]), 1 / 16))[0]
        np.testing.assert_allclose(r.logits, want, rtol=RTOL,
                                   atol=RTOL * np.abs(want).max(),
                                   err_msg=f"tick {r.frame_idx}")
    seg = _served(bucket_model, params, kernel).run_segment("cam", frames)
    assert len(seg) == len(per_tick)
    for a, b in zip(per_tick, seg):
        np.testing.assert_array_equal(a.counts, b.counts)
        np.testing.assert_allclose(b.logits, a.logits, rtol=RTOL,
                                   atol=RTOL * np.abs(a.logits).max(),
                                   err_msg=f"tick {a.frame_idx}")


def test_int8_refuses_grouped_conv():
    model = _mbv2()
    with pytest.raises(ValueError, match=r"head node 'block_0_depthwise': grouped "
                                         r"convolution \(groups=8\) has no int8"):
        model.replace(precision="int8")
    spec = FPCASpec(image_h=20, image_w=20, out_channels=4, kernel=5, stride=5)
    with pytest.raises(ValueError, match=r"head\[0\]: grouped convolution"):
        fpca.FPCAModelProgram(frontend=fpca.FPCAProgram(spec=spec),
                              head=(ConvSpec(4, 3, groups=4), DenseSpec(2)),
                              precision="int8")
    # an ungrouped graph with a global pool still lowers to int8
    head = heads.HeadGraph(nodes=(
        heads.Node("conv", ConvSpec(4, 3, padding="SAME")),
        heads.Node("pool", heads.GlobalPoolSpec(), ("conv",)),
        heads.Node("fc", DenseSpec(2), ("pool",))), output="fc")
    q = fpca.FPCAModelProgram(frontend=fpca.FPCAProgram(spec=spec), head=head,
                              precision="int8")
    f32 = q.replace(precision="f32")
    hp = f32.init_head(jax.random.PRNGKey(0))
    counts = jnp.asarray(_counts((2, 4, 4, 4), 3))
    a = np.asarray(f32.apply_head(hp, counts))
    b = np.asarray(q.apply_head(q.bind_head_params(hp), counts))
    assert b.shape == a.shape == (2, 2)
    np.testing.assert_allclose(b, a, atol=0.05 * np.abs(a).max())
