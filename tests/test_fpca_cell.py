"""FPCA production cell: basis-form lowering path correctness + info math."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.adc import ADCConfig
from repro.core.fpca_sim import WeightEncoding, encode_weights, extract_windows
from repro.core.mapping import FPCASpec
from repro.kernels.fpca_conv.ops import fpca_conv_basis_jnp, pad_to_lanes
from repro.kernels.fpca_conv.ref import fpca_conv_ref


def test_basis_jnp_matches_ref(bucket_model):
    """The dry-run lowering path (flat jnp basis form) == the oracle."""
    rng = np.random.default_rng(0)
    M, n_real, N, C = 192, 75, 128, 8
    patches = np.zeros((M, N), np.float32)
    patches[:, :n_real] = rng.uniform(0, 1, (M, n_real))
    w = np.zeros((N, C), np.float32)
    w[:n_real] = rng.uniform(0, 1, (n_real, C))
    w2 = np.roll(w, 1, axis=1)
    mask = np.zeros((N,), np.float32)
    mask[:n_real] = 1.0
    bn = rng.integers(0, 20, (C,)).astype(np.float32)
    adc = ADCConfig()
    got = fpca_conv_basis_jnp(
        jnp.asarray(patches), jnp.asarray(w), jnp.asarray(w2), bucket_model,
        adc, jnp.asarray(bn), mask=jnp.asarray(mask), n_real=n_real,
    )
    want = fpca_conv_ref(
        jnp.asarray(patches), jnp.asarray(w), jnp.asarray(w2), bucket_model,
        adc, jnp.asarray(bn), mask=jnp.asarray(mask),
    )
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 1.0


@pytest.mark.slow
def test_fpca_cell_builds_on_host_mesh(bucket_model):
    from repro.launch.fpca_cell import FpcaShape, build_fpca_cell
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(1, 1)
    shape = FpcaShape("tiny", 64, 2)
    with jax.sharding.set_mesh(mesh):
        jitted, args, info = build_fpca_cell(shape, mesh, bucket_model)
        compiled = jitted.lower(*args).compile()
    assert info.model_flops() > 0
    out_sds = jax.eval_shape(jitted, *args)
    assert out_sds.shape[-1] == info.spec.out_channels
    assert compiled.cost_analysis()["flops"] > 0
