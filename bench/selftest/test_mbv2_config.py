"""The ``p2m_mobilenetv2`` configuration's head module against the program
and the tests' own reference, and the head's trace readers on hand-made
traces."""

import json
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, trace, work

CONFIG = harness.BENCH / "configs" / "p2m_mobilenetv2"


def _cfg(h: int = 560, w: int = 560) -> dict:
    cfg = json.loads(CONFIG.with_suffix(".json").read_text())
    cfg["spec"].update(image_h=h, image_w=w)
    return cfg


def _head():
    return harness.load_module(CONFIG.with_suffix(".py"))


@pytest.mark.parametrize("h, w", [(560, 560), (40, 40), (40, 80)])
def test_mbv2_head_flops_are_twice_the_zoo_models_macs(h, w):
    from repro.core import analysis
    from repro.core.mapping import FPCASpec
    from repro.fpca import zoo

    cfg, head = _cfg(h, w), _head()
    model = zoo.build_model(head.zoo_cfg(cfg), spec=FPCASpec(**cfg["spec"]))
    assert head.head_flops(cfg) == 2 * analysis.head_flops(model)["macs"]
    if h == 560:
        assert head.head_flops(cfg) == 2 * 281_132_416
        assert work.model_flops_per_frame(cfg, head.head_flops(cfg)) == (
            15_052_800 + 562_264_832)
    # the program binds the parameters the benchmark draws
    params = jax.eval_shape(lambda k: head.make_head_params(k, cfg), jax.random.PRNGKey(0))
    want = {n: {k: v.shape for k, v in p.items()} for n, p in params.items()}
    got = jax.eval_shape(lambda k: model.init_head(k), jax.random.PRNGKey(0))
    assert want == {n: {k: v.shape for k, v in p.items()} for n, p in got.items()}


def test_mbv2_head_work_is_unpadded():
    cfg, head = _cfg(), _head()
    flops, nbytes = head.head_work(cfg, 128)
    assert flops == 128 * 562_264_832
    # the float32 effective map in, two float32 logits out
    assert nbytes == 128 * 4 * (112 * 112 * 8 + 2)


def test_mbv2_head_equals_the_tests_reference():
    ref = harness.load_module(harness.ROOT / "tests" / "mobilenetv2_ref.py")
    cfg, head = _cfg(40, 40), _head()
    params = head.make_head_params(jax.random.PRNGKey(3), cfg)
    eff = jnp.asarray(np.random.default_rng(0).integers(0, 33, (3, 8, 8, 8)), jnp.float32)
    got = np.asarray(head.head(params, eff, cfg))
    want = np.asarray(ref.forward(params, eff, cfg["head"]["input_scale"]))
    np.testing.assert_array_equal(got, want)
    # the bfloat16 control runs the same function one precision below
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    ctl = np.asarray(head.head(low, eff.astype(jnp.bfloat16), cfg,
                               precision=jax.lax.Precision.DEFAULT), np.float32)
    assert ctl.shape == got.shape and not np.array_equal(ctl, got)


def _reader(base):
    return harness.load_module(harness.BENCH / "metrics" / f"{base}.py")


HEAD_OP = ("%fusion.20 = bf16[128,56,56,96]{0,3,2,1:T(8,128)(2,1)} fusion(bf16[128,56,56,24] "
           "%fusion.91, f32[144,1,1,24] %copy-done.7), kind=kOutput, calls=%fused_computation")


def _ctx(peak=True, frames=256.0):
    dev = trace.Device(ops=[(0, 4_000_000, HEAD_OP),
                            (4_000_000, 5_000_000, "%fpca_conv.1 = f32[8] custom-call(), "
                             'custom_call_target="tpu_custom_call"'),
                            (5_000_000, 6_000_000, "%slice_multiply_fusion = f32[8] fusion("
                             "f32[8] %fpca_conv.1), kind=kLoop, calls=%fused_computation"),
                            (6_000_000, 7_000_000, HEAD_OP)])
    dev.busy = trace._merge((s, e) for s, e, _ in dev.ops)
    tr = trace.Trace(window=(0, 10_000_000), devices={0: dev}, host=[])
    spans = [{"event": "span", "span": "serve_tick", "dur_s": 0.01}] * 2
    return SimpleNamespace(
        trace=tr, spans=spans, stats={"frames": frames}, cfg=_cfg(), work=work,
        peak={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9} if peak else None,
        note=lambda msg: None)


def test_head_device_ms_sums_the_heads_output_fusions_per_tick():
    # 5 ms of head operations over 2 ticks; the frontend's kernel and loop
    # fusion are not the head's
    assert _reader("head_device_ms").read(_ctx()) == pytest.approx(2.5)


def test_head_conv_roofline_is_flops_bound_over_head_time():
    got = _reader("head_conv_roofline").read(_ctx())
    assert got == pytest.approx(100.0 * 256 * 562_264_832 / 197e12 / 5e-3)
    assert 0.0 < got <= 100.0


@pytest.mark.parametrize("base", ["head_device_ms", "head_conv_roofline"])
def test_head_readers_are_silent_without_head_ops(base):
    ctx = _ctx()
    ctx.trace.devices[0].ops = [op for op in ctx.trace.devices[0].ops if op[2] != HEAD_OP]
    assert _reader(base).read(ctx) is None
    assert _reader(base).read(SimpleNamespace(**{**vars(_ctx()), "trace": None})) is None
    if base == "head_conv_roofline":
        assert _reader(base).read(_ctx(peak=False)) is None
        assert _reader(base).read(_ctx(frames=0.0)) is None
