"""Temporal delta-gate math, shared between the host loop and the device.

The streaming stack keeps two copies of the gate state machine alive: the
host-side per-tick loop (:class:`repro.serving.streaming.StreamSession`) and
the device-compiled segment executor (one ``jax.lax.scan`` over K ticks with
the gate in the carry — :meth:`repro.fpca.CompiledFrontend.run_segment`).
The segment parity contract is *bit-identity, tick for tick*, and the fragile
part is the threshold comparison ``block_delta > threshold``: a 1-ulp
difference between a numpy and an XLA reduction flips a keep/skip decision
and breaks the whole downstream trace.  So there is exactly ONE
implementation of the gate numerics — the jnp functions here — and the host
path evaluates it through the per-spec jitted kernels of
:func:`host_gate_kernels` while the scan body inlines the same functions into
its trace.  Both sides therefore compare identical float32 bits against
identical float32 thresholds.

Everything in this module depends only on :mod:`repro.core.mapping` (no
serving imports), so the backend registry can build scan bodies from it
without import cycles.

State-machine semantics (mirrors ``streaming._step_gates`` exactly):

* block ages start at ``hysteresis + 1`` (everything stale);
* a block's age resets to 0 when its mean |Δ| exceeds the threshold, else
  increments — but only once a previous frame exists;
* a tick is a keyframe on the first frame, then whenever
  ``keyframe_interval > 0`` and ``frame_idx % keyframe_interval == 0``;
* keep = everything on a keyframe, else ``age <= hysteresis``; keyframes do
  NOT reset ages (a static scene goes quiet again right after the refresh).
"""

from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mapping import FPCASpec, output_dims

__all__ = [
    "GateCarry",
    "block_grid",
    "effective_frame",
    "block_reduce_mean",
    "block_delta",
    "window_mask_from_blocks",
    "gate_tick",
    "init_gate_carry",
    "host_gate_kernels",
]


class GateCarry(NamedTuple):
    """Device-resident delta-gate state (the scan carry's gate slice).

    ``has_prev`` gates the age update and forces the first-frame keyframe;
    ``prev_eff`` is the previous *effective* (binned grayscale) frame;
    ``age`` counts frames since each block last changed (int32 — identical
    to the host's int64 trajectory for any stream shorter than 2^31 ticks);
    ``frame_idx`` drives the keyframe cadence.
    """

    has_prev: jax.Array   # () bool
    prev_eff: jax.Array   # (eff_h, eff_w) float32
    age: jax.Array        # (bh, bw) int32
    frame_idx: jax.Array  # () int32


def block_grid(spec: FPCASpec) -> tuple[int, int]:
    """Shape of the per-block keep/age grids (periphery SRAM geometry)."""
    b = spec.skip_block
    return math.ceil(spec.eff_h / b), math.ceil(spec.eff_w / b)


def effective_frame(frame: jax.Array, spec: FPCASpec) -> jax.Array:
    """Frame as the pixel array sees it: binned (average pool) grayscale."""
    img = jnp.mean(jnp.asarray(frame, jnp.float32), axis=-1)
    b = spec.binning
    if b > 1:
        h, w = img.shape
        img = img[: h // b * b, : w // b * b].reshape(
            h // b, b, w // b, b
        ).mean((1, 3))
    return img


def block_reduce_mean(x: jax.Array, block: int) -> jax.Array:
    """Mean over ``block x block`` tiles (ragged edge tiles average their
    real pixels only), shape ``(ceil(h/b), ceil(w/b))``."""
    h, w = x.shape
    bh, bw = math.ceil(h / block), math.ceil(w / block)
    padded = jnp.pad(x, ((0, bh * block - h), (0, bw * block - w)))
    sums = padded.reshape(bh, block, bw, block).sum((1, 3))
    ones = np.zeros((bh * block, bw * block), np.float32)
    ones[:h, :w] = 1.0
    counts = ones.reshape(bh, block, bw, block).sum((1, 3))
    return sums / counts


def block_delta(
    prev_eff: jax.Array, cur_eff: jax.Array, spec: FPCASpec
) -> jax.Array:
    """Mean absolute per-block change between two effective frames."""
    return block_reduce_mean(jnp.abs(cur_eff - prev_eff), spec.skip_block)


def window_mask_from_blocks(block_keep: jax.Array, spec: FPCASpec) -> jax.Array:
    """Trace-friendly twin of :func:`repro.core.mapping.active_window_mask`.

    A window executes iff *any* of its pixels lies in a kept block.  Window
    footprints that run past the effective frame read as not-kept — the same
    clipping the numpy slicing fallback applies.  Returns ``(h_o, w_o)``
    bool.
    """
    b = spec.skip_block
    h_o, w_o = output_dims(spec)
    n, s = spec.max_kernel, spec.stride
    pixel = jnp.repeat(jnp.repeat(block_keep, b, axis=0), b, axis=1)[
        : spec.eff_h, : spec.eff_w
    ]
    r_idx = (np.arange(h_o)[:, None] * s + np.arange(n)[None, :]).reshape(-1)
    c_idx = (np.arange(w_o)[:, None] * s + np.arange(n)[None, :]).reshape(-1)
    rows = jnp.take(
        pixel, jnp.asarray(r_idx), axis=0, mode="fill", fill_value=False
    )
    patch = jnp.take(
        rows, jnp.asarray(c_idx), axis=1, mode="fill", fill_value=False
    )
    return patch.reshape(h_o, n, w_o, n).any(axis=(1, 3))


def init_gate_carry(spec: FPCASpec, hysteresis: int) -> GateCarry:
    """Fresh gate state: no previous frame, every block stale (so the first
    non-keyframe tick after warm-up drops unchanged blocks, like the host)."""
    bh, bw = block_grid(spec)
    return GateCarry(
        has_prev=jnp.zeros((), bool),
        prev_eff=jnp.zeros((spec.eff_h, spec.eff_w), jnp.float32),
        age=jnp.full((bh, bw), int(hysteresis) + 1, jnp.int32),
        frame_idx=jnp.zeros((), jnp.int32),
    )


def gate_tick(
    spec: FPCASpec,
    carry: GateCarry,
    cur_eff: jax.Array,
    threshold: jax.Array,
    hysteresis: jax.Array,
    keyframe_interval: jax.Array,
) -> tuple[GateCarry, jax.Array, jax.Array]:
    """One delta-gate transition; gate knobs enter *traced* so retuning the
    threshold (the boundary servo) or the cadence never recompiles.

    Returns ``(new_carry, keep_blocks (bh, bw) bool, keyframe () bool)``.
    """
    delta = block_delta(carry.prev_eff, cur_eff, spec)
    changed = delta > threshold
    age = jnp.where(
        carry.has_prev,
        jnp.where(changed, jnp.zeros_like(carry.age), carry.age + 1),
        carry.age,
    )
    ki = keyframe_interval
    keyframe = jnp.logical_or(
        ~carry.has_prev,
        jnp.logical_and(ki > 0, carry.frame_idx % jnp.maximum(ki, 1) == 0),
    )
    keep = jnp.logical_or(keyframe, age <= hysteresis)
    new_carry = GateCarry(
        has_prev=jnp.ones((), bool),
        prev_eff=cur_eff,
        age=age,
        frame_idx=carry.frame_idx + 1,
    )
    return new_carry, keep, keyframe


class HostGateKernels(NamedTuple):
    """Per-spec jitted gate kernels for the host per-tick loop — the SAME
    jnp numerics the scan body inlines, so host and device gate decisions
    compare identical float32 bits.  ``step`` fuses the effective-frame and
    block-delta stages into ONE dispatch (the serving hot loop blocks on the
    gate result before it can build the tick's window mask, so per-call
    overhead is paid synchronously).  ``step_batch`` is its vmapped twin:
    a fleet tick gates every stream of a group in one dispatch instead of
    one per stream, which is what keeps the per-tick host cost flat as the
    fleet grows (the weak-scaling lane of ``benchmarks/fleet_bench.py``).
    It compiles once per fleet size; the per-row math is the identical
    trace, so batched and solo gate decisions agree bit for bit.

    The new effective frame a step returns is meant to stay on the device
    as the next step's ``prev_eff``: the caller reads back only the block
    |Δ| grid the thresholds compare.  The ``*_signed`` variants also return
    the signed block-mean change, the polarity an event tap needs."""

    eff: Callable        # frame -> effective frame
    delta: Callable      # (prev_eff, cur_eff) -> block |Δ| grid
    step: Callable       # (prev_eff, frame) -> (cur_eff, block |Δ| grid)
    step_batch: Callable  # (n, ...) stacked twin of ``step``
    step_signed: Callable  # (prev_eff, frame) -> (cur_eff, |Δ|, signed Δ)
    step_batch_signed: Callable  # (n, ...) stacked twin of ``step_signed``


@functools.lru_cache(maxsize=None)
def host_gate_kernels(spec: FPCASpec) -> HostGateKernels:
    eff = jax.jit(lambda frame: effective_frame(frame, spec))
    delta = jax.jit(lambda prev, cur: block_delta(prev, cur, spec))

    def _step(prev_eff, frame):
        cur = effective_frame(frame, spec)
        return cur, block_delta(prev_eff, cur, spec)

    def _step_signed(prev_eff, frame):
        cur, delta_blocks = _step(prev_eff, frame)
        signed = block_reduce_mean(cur - prev_eff, spec.skip_block)
        return cur, delta_blocks, signed

    return HostGateKernels(
        eff, delta, jax.jit(_step), jax.jit(jax.vmap(_step)),
        jax.jit(_step_signed), jax.jit(jax.vmap(_step_signed)),
    )
