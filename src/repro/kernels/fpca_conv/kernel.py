"""Pallas TPU kernel for the FPCA analog convolution (bucket-select model).

TPU-native formulation (DESIGN.md §2): every windowed polynomial sum
factors over the monomial basis,

    sum_j f(I_j, W_j) = sum_{a,b} c_ab * <I_patch^a, W^b>,

so the whole non-linear analog conv = a bank of power-basis contractions
combined by sigmoid bucket gates.  The bank is rank-structured:

* (a=0, b)   -> per-channel constants  ``cs[b, c] = sum_j mask_j W[j,c]^b``
                (precomputed on host, no FLOPs in kernel);
* (a, b=0)   -> per-window vectors     ``rv[a, m] = <I^a, mask>``
                ((bm, N) @ (N, 1) — VPU-cheap);
* (a,b >= 1) -> true MXU matmuls, only (1,1), (1,2), (2,1) for the paper's
                degree-3 bucket surfaces;
* step-1 estimate -> one (bm, 15) @ (15, bc) matmul on window/channel means.

Both weight phases (CH_i positive cycle, CH_i_bar negative) are fused in one
kernel invocation together with the SS-ADC up/down counting epilogue, so the
patch tile is read from VMEM once per output tile.

Grid: (M / block_m, C / block_c); each program owns one output tile.
VMEM per program (defaults bm=256, bc=128, N=128):
  patches 128 KiB + 2 x w_pows 256 KiB + gates/acc scratch < 1 MiB  — far
  under the ~16 MiB budget, leaving headroom for double buffering.

Region skipping (§3.4.5) enters as a *row-compacted* patch matrix: the ops
layer gathers only the windows whose blocks survived the temporal delta gate
(padded to a static bucket), so the grid itself shrinks — fewer programs, not
masked-out results.  ``row_valid`` marks the real rows of the compacted
bucket; it multiplies the counts inside the fused epilogue so bucket-padding
rows scatter back as exact zeros (0.0/1.0 multiply — bit-exact on the kept
rows).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.adc import ADCConfig
from repro.core.curvefit import BucketCurvefitModel

__all__ = ["fpca_conv_pallas", "precompute_weight_planes"]

# Monomial pairs of the degree-3 bucket surfaces, grouped by rank structure.
_MM_PAIRS = ((1, 1), (1, 2), (2, 1))   # true matmuls
_VEC_AS = (1, 2, 3)                    # (a, 0): per-window vectors
_CONST_BS = (0, 1, 2, 3)               # (0, b): per-channel constants


def _bucket_tables(model: BucketCurvefitModel) -> dict[str, np.ndarray]:
    """Static per-model tables: combine coefficients keyed by (a, b) pair."""
    exps = [tuple(int(v) for v in e) for e in model.bucket_exps]
    coeffs = np.asarray(model.bucket_coeffs)          # (n_buckets, n_terms)
    v_c = np.asarray(model.v_centers)
    by_pair = {pair: coeffs[:, exps.index(pair)] / model.n_sweep for pair in exps}
    const = v_c * (1.0 - model.n_pixels / model.n_sweep)   # B_i affine offset
    return {"by_pair": by_pair, "const": const}


def precompute_weight_planes(
    w: jax.Array, mask: jax.Array, model: BucketCurvefitModel
) -> dict[str, jax.Array]:
    """Host-side precomputation for one weight phase (w: (N, C), mask: (N,)).

    Returns:
      w_pows : (2, N, C) — masked W^1, W^2 (the matmul operands)
      cs     : (4, C)    — per-channel constants sum_j mask W^b, b = 0..3
      aw     : (n_avg_terms, C) — f_avg coeffs folded with meanW powers
    """
    wm = w * mask[:, None]
    n_real = jnp.sum(mask)
    w_pows = jnp.stack([wm, wm * wm])                       # b = 1, 2
    cs = jnp.stack([mask @ jnp.ones_like(w), mask @ w, mask @ (w * w), mask @ (w * w * w)])
    mean_w = (mask @ w) / n_real                            # (C,)
    avg_exps = model.f_avg.exps
    aw = jnp.stack(
        [model.f_avg.coeffs[t] * mean_w ** int(avg_exps[t, 1]) for t in range(len(avg_exps))]
    )                                                       # (T_avg, C)
    return {"w_pows": w_pows, "cs": cs, "aw": aw}


def _fpca_kernel(
    # refs (order matches in_specs below)
    patches_ref, mask_ref, valid_ref,
    wp_pows_ref, wp_cs_ref, wp_aw_ref,
    wn_pows_ref, wn_cs_ref, wn_aw_ref,
    bn_ref,
    out_ref,
    *,
    tables: dict[str, Any],
    avg_a_exps: tuple[int, ...],
    n_real: float,
    n_buckets: int,
    sharpness: float,
    v_range: float,
    lsb: float,
    levels: int,
):
    x = patches_ref[...]                                    # (bm, N)
    maskv = mask_ref[...]                                   # (N, 1)
    x2 = x * x
    x3 = x2 * x
    xpows = {1: x, 2: x2, 3: x3}
    # per-window vectors <I^a, mask> and window mean
    rv = {a: jnp.dot(xpows[a], maskv) for a in _VEC_AS}     # (bm, 1) each
    mean_i = rv[1] / n_real                                 # (bm, 1)
    mi_pows = [mean_i ** a for a in avg_a_exps]             # list of (bm, 1)
    a_i = jnp.concatenate(mi_pows, axis=1)                  # (bm, T_avg)

    edges = np.arange(n_buckets, dtype=np.float32) / n_buckets
    coeff_by_pair = tables["by_pair"]
    const_b = tables["const"]

    def one_phase(pows_ref, cs_ref, aw_ref):
        # true matmuls (MXU)
        mm = {
            (a, b): jnp.dot(xpows[a], pows_ref[b - 1], preferred_element_type=jnp.float32)
            for (a, b) in _MM_PAIRS
        }                                                   # (bm, bc)
        cs = cs_ref[...]                                    # (4, bc)
        v_est = jnp.dot(a_i, aw_ref[...], preferred_element_type=jnp.float32)
        xg = v_est / v_range                                # (bm, bc)
        v_pred = jnp.zeros_like(xg)
        for i in range(n_buckets):
            gate = (
                jax.nn.sigmoid(sharpness * (xg - edges[i]))
                + jax.nn.sigmoid(sharpness * (edges[i] + 1.0 / n_buckets - xg))
                - 1.0
            )
            acc = jnp.full_like(xg, const_b[i])
            for (a, b), c in coeff_by_pair.items():
                ci = float(c[i])
                if a == 0:
                    acc += ci * cs[b][None, :]
                elif b == 0:
                    acc += ci * rv[a]
                else:
                    acc += ci * mm[(a, b)]
            v_pred += gate * acc
        return v_pred

    v_pos = one_phase(wp_pows_ref, wp_cs_ref, wp_aw_ref)
    v_neg = one_phase(wn_pows_ref, wn_cs_ref, wn_aw_ref)
    # SS-ADC epilogue: up/down count + BN counter init + ReLU/saturation clamp;
    # row validity (region-skip bucket padding) zeroes dead rows in-place —
    # a 0.0/1.0 multiply, exact on valid rows.
    up = jnp.clip(jnp.round(v_pos / lsb), 0, levels - 1)
    down = jnp.clip(jnp.round(v_neg / lsb), 0, levels - 1)
    out_ref[...] = valid_ref[...] * jnp.clip(bn_ref[...] + up - down, 0, levels - 1)


def fpca_conv_pallas(
    patches: jax.Array,
    w_pos: jax.Array,
    w_neg: jax.Array,
    model: BucketCurvefitModel,
    adc: ADCConfig,
    bn_offset: jax.Array,
    mask: jax.Array | None = None,
    *,
    n_real: int | None = None,
    row_valid: jax.Array | None = None,
    block_m: int = 256,
    block_c: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """FPCA analog conv counts, shape (M, C). See module docstring.

    ``patches (M, N)``, ``w_pos/w_neg (N, C)``, ``bn_offset (C,)``; N may be
    zero-padded — pass ``mask`` marking real pixel slots and ``n_real`` (the
    static count of real slots; required when tracing with a traced mask).
    ``row_valid (M,)`` marks real rows of a region-skip compacted bucket;
    rows with 0 come out as exact zeros (default: all rows valid).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    M, N = patches.shape
    C = w_pos.shape[1]
    if mask is None:
        mask = jnp.ones((N,), jnp.float32)
        n_real = n_real or N
    if n_real is None:
        n_real = int(np.sum(np.asarray(mask)))

    # ---- host-side padding to tile multiples --------------------------------
    Mp = -(-M // block_m) * block_m
    Cp = -(-C // block_c) * block_c
    patches_p = jnp.pad(patches.astype(jnp.float32), ((0, Mp - M), (0, 0)))
    w_pos_p = jnp.pad(w_pos.astype(jnp.float32), ((0, 0), (0, Cp - C)))
    w_neg_p = jnp.pad(w_neg.astype(jnp.float32), ((0, 0), (0, Cp - C)))
    bn_p = jnp.pad(bn_offset.astype(jnp.float32), (0, Cp - C))[None, :]
    if row_valid is None:
        row_valid = jnp.ones((M,), jnp.float32)
    valid_p = jnp.pad(row_valid.astype(jnp.float32), (0, Mp - M))[:, None]

    pp = precompute_weight_planes(w_pos_p, mask, model)
    pn = precompute_weight_planes(w_neg_p, mask, model)
    tables = _bucket_tables(model)
    avg_a_exps = tuple(int(a) for a, _ in model.f_avg.exps)
    t_avg = len(avg_a_exps)

    kernel = functools.partial(
        _fpca_kernel,
        tables=tables,
        avg_a_exps=avg_a_exps,
        n_real=float(n_real),
        n_buckets=model.n_buckets,
        sharpness=model.sharpness,
        v_range=model.v_range,
        lsb=adc.lsb,
        levels=adc.levels,
    )
    grid = (Mp // block_m, Cp // block_c)
    counts = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, N), lambda m, c: (m, 0)),       # patches
            pl.BlockSpec((N, 1), lambda m, c: (0, 0)),             # mask
            pl.BlockSpec((block_m, 1), lambda m, c: (m, 0)),       # row validity
            pl.BlockSpec((2, N, block_c), lambda m, c: (0, 0, c)),  # pos W^b
            pl.BlockSpec((4, block_c), lambda m, c: (0, c)),       # pos consts
            pl.BlockSpec((t_avg, block_c), lambda m, c: (0, c)),   # pos f_avg
            pl.BlockSpec((2, N, block_c), lambda m, c: (0, 0, c)),  # neg W^b
            pl.BlockSpec((4, block_c), lambda m, c: (0, c)),       # neg consts
            pl.BlockSpec((t_avg, block_c), lambda m, c: (0, c)),   # neg f_avg
            pl.BlockSpec((1, block_c), lambda m, c: (0, c)),       # bn offset
        ],
        out_specs=pl.BlockSpec((block_m, block_c), lambda m, c: (m, c)),
        out_shape=jax.ShapeDtypeStruct((Mp, Cp), jnp.float32),
        interpret=interpret,
        name="fpca_conv",
    )(
        patches_p,
        mask[:, None].astype(jnp.float32),
        valid_p,
        pp["w_pows"], pp["cs"], pp["aw"],
        pn["w_pows"], pn["cs"], pn["aw"],
        bn_p,
    )
    return counts[:M, :C]
