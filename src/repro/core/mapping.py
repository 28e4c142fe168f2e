"""FPCA mapping & scheduling model (paper §3.3--§3.4).

Reproduces, cycle by cycle, how the FPCA control fabric maps a first-layer
convolution onto the pixel array:

* ``CH_i`` / ``CH_i_bar`` — output-channel select; the two lines of a channel
  fire in consecutive cycles (positive-kernel phase, then negative), which is
  the factor 2 in Eq. 1;
* ``ColP_i`` — maps kernel column *i* onto a pixel column (horizontal stride);
* ``RS`` / ``SW`` — row/column unit-pixel enables (vertical stride, region
  skipping);
* the switch matrix routes the ``n`` SM lines so that adjacent pixel rows see
  the right kernel rows (vertical striding re-routes it).

The numerics of a cycle run batched on the MXU (all parallel windows of the
cycle at once); the *schedule* here is what the energy/latency analysis and
the Eq. 1 property tests consume.

Key hardware facts encoded (and tested):

* the physical kernel footprint is always the max ``n x n`` — smaller logical
  kernels are implemented by writing zero weights (paper §3.4.1), so the
  output grid (Eq. 8) is computed with ``n``, not the logical ``k``;
* windows computed in the same cycle share a ``ColP`` phase and are spaced
  ``lcm(S, n)`` pixel columns apart (disjoint column groups), giving
  ``lcm(S, n) / S`` horizontal phases per output row — the last factor of
  Eq. 1: ``N_C = 2 * h_o * c_o * lcm(S, n) / S``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Iterator

import numpy as np

__all__ = [
    "FPCASpec", "Cycle", "n_cycles", "output_dims", "schedule",
    "active_window_mask", "active_window_masks",
]


@dataclasses.dataclass(frozen=True)
class FPCASpec:
    """Static configuration of one FPCA first-layer convolution."""

    image_h: int
    image_w: int
    out_channels: int
    kernel: int                 # logical kernel size k (k <= max_kernel)
    stride: int
    max_kernel: int = 5         # physical n (weight-die provisioning)
    in_channels: int = 3        # RGB planes, processed concurrently (§3.2)
    padding: int = 0
    binning: int = 1            # pixel binning factor (Fig. 9(b))
    skip_block: int = 8         # region-skipping block granularity (§3.4.5)

    def __post_init__(self) -> None:
        if self.kernel > self.max_kernel:
            raise ValueError(f"kernel {self.kernel} exceeds max_kernel {self.max_kernel}")
        if not (1 <= self.stride <= self.max_kernel):
            raise ValueError("stride must be in [1, max_kernel] (paper §3.4.3)")

    # -- derived geometry -----------------------------------------------------
    @property
    def eff_h(self) -> int:
        return self.image_h // self.binning

    @property
    def eff_w(self) -> int:
        return self.image_w // self.binning

    @property
    def n_active_pixels(self) -> int:
        """Pixels activated per window read — always the full n*n*in_ch region."""
        return self.max_kernel * self.max_kernel * self.in_channels

    @property
    def horizontal_phases(self) -> int:
        """lcm(S, n) / S — ColP phases needed to cover one output row."""
        return math.lcm(self.stride, self.max_kernel) // self.stride

    @property
    def weights_per_column(self) -> int:
        """NVM devices per pixel column in the weight die (§3.2)."""
        return 2 * self.max_kernel**2 * self.in_channels * self.out_channels


def output_dims(spec: FPCASpec) -> tuple[int, int]:
    """Eq. 8 with the *physical* kernel n (zero-padded logical kernels)."""
    n, s, p = spec.max_kernel, spec.stride, spec.padding
    h_o = (spec.eff_h - n + 2 * p) // s + 1
    w_o = (spec.eff_w - n + 2 * p) // s + 1
    if h_o <= 0 or w_o <= 0:
        raise ValueError("image smaller than physical kernel footprint")
    return h_o, w_o


def n_cycles(spec: FPCASpec) -> int:
    """Eq. 1: ``N_C = 2 * h_o * c_o * lcm(S, n) / S``."""
    h_o, _ = output_dims(spec)
    return 2 * h_o * spec.out_channels * spec.horizontal_phases


@dataclasses.dataclass(frozen=True)
class Cycle:
    """One read cycle of the rolling-shutter convolution schedule."""

    sign: int                   # +1: CH_i phase, -1: CH_i_bar phase
    channel: int                # output channel (CH line index)
    out_row: int                # output row r (RS group)
    phase: int                  # ColP phase p in [0, lcm(S,n)/S)
    window_cols: np.ndarray     # output-column indices computed in parallel

    stride: int = 1
    max_kernel: int = 5

    @property
    def colp_line(self) -> int:
        """ColP line pulled up in this cycle: which kernel column is mapped
        onto the first pixel column of each window group (§3.4.3 — e.g. for
        s=1, ColP1 activation is followed by ColP2 as the kernel slides)."""
        return (self.phase * self.stride) % self.max_kernel


def schedule(spec: FPCASpec) -> Iterator[Cycle]:
    """Yield the full cycle schedule; ``len(list(...)) == n_cycles(spec)``.

    Parallel windows of a cycle: output columns ``w`` whose horizontal start
    ``x = w * S`` satisfies ``x ≡ p*S (mod lcm(S, n))`` — their ``n``-wide
    column groups are disjoint, so they can share the cycle (§3.4.3).
    """
    h_o, w_o = output_dims(spec)
    n, s = spec.max_kernel, spec.stride
    period = math.lcm(s, n)
    phases = spec.horizontal_phases
    all_cols = np.arange(w_o)
    for channel in range(spec.out_channels):
        for out_row in range(h_o):
            for phase in range(phases):
                cols = all_cols[(all_cols * s) % period == phase * s]
                for sign in (+1, -1):
                    yield Cycle(
                        sign=sign,
                        channel=channel,
                        out_row=out_row,
                        phase=phase,
                        window_cols=cols,
                        stride=s,
                        max_kernel=n,
                    )


def active_window_mask(spec: FPCASpec, block_mask: np.ndarray | None) -> np.ndarray:
    """Region skipping (§3.4.5): which output windows actually execute.

    ``block_mask`` is the per-block keep/skip grid stored in the periphery
    SRAMs, shape ``(ceil(H/B), ceil(W/B))`` booleans (True = keep).  A window
    executes iff *any* of its pixels lies in a kept block (RS/SW lines for
    fully-skipped regions are never raised).

    Returns a boolean ``(h_o, w_o)`` mask: row 0 of
    :func:`active_window_masks` on the one grid.
    """
    if block_mask is None:
        return np.ones(output_dims(spec), dtype=bool)
    return active_window_masks(spec, np.asarray(block_mask)[None])[0]


@functools.lru_cache(maxsize=None)
def _window_block_taps(
    spec: FPCASpec,
) -> tuple[tuple[tuple[np.ndarray, ...], np.ndarray | None], ...]:
    """Per axis (rows, then columns): the block indices each output window
    reads, and which windows hold any in-bounds pixel (None when all do).

    Window ``r`` covers pixels ``r*s .. min(r*s + n, eff) - 1`` (offsets
    ignore ``padding``; a footprint past the frame is clipped), a
    contiguous run of blocks ``lo .. hi``.  Tap ``k`` is block
    ``min(lo + k, hi)``, so ORing the taps ORs exactly the run."""
    n, s, b = spec.max_kernel, spec.stride, spec.skip_block
    axes = []
    for eff, out in zip((spec.eff_h, spec.eff_w), output_dims(spec)):
        start = np.arange(out) * s
        stop = np.minimum(start + n, eff)
        inside = stop > start
        lo = np.where(inside, start // b, 0)
        hi = np.where(inside, (stop - 1) // b, 0)
        taps = tuple(
            np.minimum(lo + k, hi) for k in range(int((hi - lo).max()) + 1)
        )
        axes.append((taps, None if inside.all() else inside))
    return tuple(axes)


def active_window_masks(spec: FPCASpec, block_masks: np.ndarray) -> np.ndarray:
    """:func:`active_window_mask` over a stack of block grids: ``(n, bh,
    bw)`` -> ``(n, h_o, w_o)`` booleans, row ``j`` the windows that grid
    ``j`` executes.  A window reads the run of blocks its footprint covers
    on each axis (:func:`_window_block_taps`), so the grid is an OR of a
    few shifted gathers per axis; a window with no in-bounds pixel is not
    kept."""
    masks = np.asarray(block_masks, dtype=bool)
    b = spec.skip_block
    exp = (math.ceil(spec.eff_h / b), math.ceil(spec.eff_w / b))
    if masks.ndim != 3 or masks.shape[1:] != exp:
        raise ValueError(f"block_mask shape {masks.shape[1:]} != {exp}")
    (row_taps, row_in), (col_taps, col_in) = _window_block_taps(spec)
    rows = masks[:, row_taps[0]]
    for idx in row_taps[1:]:
        rows |= masks[:, idx]
    if row_in is not None:
        rows &= row_in[:, None]
    out = rows[:, :, col_taps[0]]
    for idx in col_taps[1:]:
        out |= rows[:, :, idx]
    if col_in is not None:
        out &= col_in
    return out


def n_cycles_with_skipping(spec: FPCASpec, block_mask: np.ndarray | None) -> int:
    """Executed cycles under region skipping: a cycle fires iff it contains
    at least one active window (the RS/SW gating is row/phase-granular)."""
    if block_mask is None:
        return n_cycles(spec)
    mask = active_window_mask(spec, block_mask)
    h_o, w_o = mask.shape
    n, s = spec.max_kernel, spec.stride
    period = math.lcm(s, n)
    executed_row_phases = 0
    all_cols = np.arange(w_o)
    for r in range(h_o):
        for phase in range(spec.horizontal_phases):
            cols = all_cols[(all_cols * s) % period == phase * s]
            if mask[r, cols].any():
                executed_row_phases += 1
    return 2 * spec.out_channels * executed_row_phases
