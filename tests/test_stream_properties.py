"""Property-test sweep for the adaptive streaming control plane.

Drawn via :mod:`_hypothesis_compat` (real hypothesis when installed, the
deterministic seeded-grid fallback otherwise), pinning the invariants the
streaming stack leans on:

* :func:`window_bucket` — power-of-two (or capped), monotone in the kept
  count, never smaller than the kept count, exact at the pow-2 boundaries
  ``±1`` (the flap-prone edges).
* :func:`block_delta_mask` / :class:`StreamSession` gating — output shape
  matches the periphery block grid, a keyframe tick keeps every block, and
  hysteresis never drops a block younger than ``hysteresis`` frames.
* :class:`StickyBucket` — always big enough for the tick's kept windows,
  shrinks only after ``patience`` consecutive under-full ticks, and
  ``patience=1`` reproduces the stateless bucket exactly.
* :class:`GateController` — threshold clamped to its configured range, the
  per-tick log-step bounded by ``max_step``, keyframe ticks never actuate.
"""

from __future__ import annotations

import math

import numpy as np
from _hypothesis_compat import given, settings, st

from repro.core.mapping import FPCASpec, active_window_mask, active_window_masks, output_dims
from repro.kernels.fpca_conv.ops import StickyBucket, window_bucket
from repro.serving.control import GateController, GateControllerConfig
from repro.serving.streaming import DeltaGateConfig, StreamSession, block_delta_mask


def _spec(kernel: int = 5, stride: int = 5, binning: int = 1, hw: int = 24) -> FPCASpec:
    return FPCASpec(
        image_h=hw, image_w=hw, out_channels=4, kernel=kernel, stride=stride,
        binning=binning,
    )


# ---------------------------------------------------------------------------
# window_bucket invariants
# ---------------------------------------------------------------------------


@settings(max_examples=60)
@given(n_keep=st.integers(0, 4096), m_total=st.integers(1, 4096))
def test_window_bucket_invariants(n_keep, m_total):
    n_keep = min(n_keep, m_total)           # masks never keep more than exists
    bucket = window_bucket(n_keep, m_total)
    # bounded: holds every kept window, never exceeds the grid
    assert max(n_keep, 1) <= bucket <= m_total
    # pow-2 unless capped at the grid size (the dense-fallback case)
    assert bucket == m_total or (bucket & (bucket - 1)) == 0
    # tight: no more than the next pow-2 of the kept count
    assert bucket <= 1 << (max(n_keep, 1) - 1).bit_length()
    # monotone in the kept count
    if n_keep < m_total:
        assert window_bucket(n_keep + 1, m_total) >= bucket


@settings(max_examples=30)
@given(p=st.integers(1, 11), m_shift=st.integers(1, 3))
def test_window_bucket_exact_at_pow2_boundaries(p, m_shift):
    pow2 = 1 << p
    m_total = pow2 << m_shift               # grid strictly above the boundary
    assert window_bucket(pow2, m_total) == pow2
    # pow2-1 rounds back up to pow2 — except 1, which is itself a bucket
    assert window_bucket(pow2 - 1, m_total) == (pow2 if pow2 > 2 else 1)
    assert window_bucket(pow2 + 1, m_total) == min(2 * pow2, m_total)


# ---------------------------------------------------------------------------
# block_delta_mask / StreamSession gate invariants
# ---------------------------------------------------------------------------


@settings(max_examples=12, deadline=None)
@given(
    kernel=st.integers(3, 5),
    stride=st.integers(2, 5),
    binning=st.sampled_from([1, 2]),
    threshold=st.floats(1e-3, 0.5),
    seed=st.integers(0, 2**16),
)
def test_block_delta_mask_shape_and_threshold_monotone(
    kernel, stride, binning, threshold, seed
):
    spec = _spec(kernel, stride, binning)
    rng = np.random.default_rng(seed)
    a = rng.uniform(0, 1, (spec.eff_h, spec.eff_w)).astype(np.float32)
    b = rng.uniform(0, 1, (spec.eff_h, spec.eff_w)).astype(np.float32)
    mask = block_delta_mask(a, b, spec, threshold)
    bh = math.ceil(spec.eff_h / spec.skip_block)
    bw = math.ceil(spec.eff_w / spec.skip_block)
    assert mask.shape == (bh, bw) and mask.dtype == bool
    # a stricter threshold can only drop blocks, never add them
    stricter = block_delta_mask(a, b, spec, threshold * 2.0)
    assert not np.any(stricter & ~mask)
    # identical frames never flag a change
    assert not block_delta_mask(a, a, spec, threshold).any()


@settings(max_examples=10, deadline=None)
@given(
    hysteresis=st.integers(0, 3),
    keyframe_interval=st.sampled_from([0, 3, 5]),
    threshold=st.floats(0.01, 0.2),
    seed=st.integers(0, 2**16),
)
def test_session_gate_keyframe_and_hysteresis_invariants(
    hysteresis, keyframe_interval, threshold, seed
):
    """Keyframes keep all blocks; a changed block survives >= hysteresis
    extra frames; every mask matches the block grid."""
    spec = _spec()
    gate = DeltaGateConfig(
        threshold=threshold, hysteresis=hysteresis,
        keyframe_interval=keyframe_interval,
    )
    session = StreamSession("s", "cam", spec, gate)
    rng = np.random.default_rng(seed)
    bh = math.ceil(spec.eff_h / spec.skip_block)
    bw = math.ceil(spec.eff_w / spec.skip_block)
    n_frames = 12
    frames, prev_eff = [], None
    changed_at: list[np.ndarray | None] = []
    for _ in range(n_frames):
        frame = rng.uniform(0, 1, (spec.image_h, spec.image_w, 3)).astype(np.float32)
        if rng.random() < 0.4 and frames:
            frame = frames[-1]              # occasionally a static tick
        frames.append(frame)
        eff = np.asarray(frame, np.float32).mean(axis=-1)
        changed_at.append(
            block_delta_mask(prev_eff, eff, spec, threshold)
            if prev_eff is not None else None
        )
        prev_eff = eff
    masks = [session.step(f) for f in frames]
    age = np.full((bh, bw), hysteresis + 1, np.int64)
    for t, mask in enumerate(masks):
        assert mask.shape == (bh, bw)
        if changed_at[t] is not None:
            age = np.where(changed_at[t], 0, age + 1)
        keyframe = t == 0 or (keyframe_interval > 0 and t % keyframe_interval == 0)
        if keyframe:
            assert mask.all()               # keyframe tick keeps every block
        else:
            # hysteresis never drops a block younger than `hysteresis`
            young = age <= hysteresis
            assert mask[young].all()
            # and never keeps one older (no phantom blocks)
            assert not mask[~young].any()


@settings(max_examples=8)
@given(binning=st.sampled_from([1, 2]), seed=st.integers(0, 2**16))
def test_gate_mask_feeds_active_window_mask(binning, seed):
    """The gate's block grid is exactly what active_window_mask consumes."""
    spec = _spec(binning=binning)
    session = StreamSession(
        "s", "cam", spec, DeltaGateConfig(threshold=0.05, hysteresis=1)
    )
    rng = np.random.default_rng(seed)
    frame = rng.uniform(0, 1, (spec.image_h, spec.image_w, 3)).astype(np.float32)
    mask = session.step(frame)
    window = active_window_mask(spec, mask)     # raises on a shape mismatch
    assert window.all()                         # first frame = keyframe


def _window_mask_by_loop(spec: FPCASpec, block_mask: np.ndarray) -> np.ndarray:
    """Each window's (clipped) pixel footprint checked against the pixel
    grid the block mask expands to, one window at a time."""
    h_o, w_o = output_dims(spec)
    b, n, s = spec.skip_block, spec.max_kernel, spec.stride
    pixel = np.kron(block_mask, np.ones((b, b), bool))[: spec.eff_h, : spec.eff_w]
    mask = np.zeros((h_o, w_o), bool)
    for r in range(h_o):
        for c in range(w_o):
            mask[r, c] = pixel[r * s : r * s + n, c * s : c * s + n].any()
    return mask


@settings(max_examples=40, deadline=None)
@given(
    max_kernel=st.integers(1, 6), kernel=st.integers(1, 6), stride=st.integers(1, 6),
    padding=st.integers(0, 3), binning=st.integers(1, 3), skip_block=st.integers(1, 9),
    image_h=st.integers(6, 41), image_w=st.integers(6, 41),
    density=st.floats(0.0, 1.0), seed=st.integers(0, 2**16),
)
def test_batched_window_masks_match_the_window_loop(
    max_kernel, kernel, stride, padding, binning, skip_block, image_h, image_w,
    density, seed,
):
    """``active_window_masks`` equals the per-window loop on every grid of a
    stack, over specs with padding, binning, blocks that do not divide the
    frame, stride below the kernel and kernel below ``max_kernel``; and
    ``active_window_mask`` is row 0 of the batched call."""
    kernel, stride = min(kernel, max_kernel), min(stride, max_kernel)
    spec = FPCASpec(
        image_h=image_h, image_w=image_w, out_channels=2, kernel=kernel,
        stride=stride, max_kernel=max_kernel, padding=padding, binning=binning,
        skip_block=skip_block,
    )
    if spec.eff_h < max_kernel or spec.eff_w < max_kernel:
        return                              # no window fits the frame
    rng = np.random.default_rng(seed)
    grid = (math.ceil(spec.eff_h / skip_block), math.ceil(spec.eff_w / skip_block))
    masks = rng.random((3, *grid)) < density
    got = active_window_masks(spec, masks)
    assert got.shape == (3, *output_dims(spec)) and got.dtype == bool
    for j in range(3):
        np.testing.assert_array_equal(got[j], _window_mask_by_loop(spec, masks[j]))
    np.testing.assert_array_equal(active_window_mask(spec, masks[0]), got[0])


# ---------------------------------------------------------------------------
# StickyBucket invariants
# ---------------------------------------------------------------------------


@settings(max_examples=25)
@given(
    patience=st.integers(1, 6),
    m_total=st.sampled_from([64, 100, 256]),
    seed=st.integers(0, 2**16),
)
def test_sticky_bucket_invariants(patience, m_total, seed):
    rng = np.random.default_rng(seed)
    sticky = StickyBucket(patience)
    plain = StickyBucket(1)
    under_streak = 0
    prev_held = None
    for _ in range(40):
        n_keep = int(rng.integers(0, m_total + 1))
        raw = window_bucket(n_keep, m_total)
        served = sticky.bucket(n_keep, m_total)
        # correctness: the served bucket always holds this tick's windows
        assert served >= raw or served == m_total
        assert max(n_keep, 1) <= served <= m_total
        # shrink discipline: only after `patience` consecutive under-full ticks
        if prev_held is not None and served < prev_held:
            assert under_streak + 1 >= patience
        under_streak = under_streak + 1 if (prev_held is not None and raw < prev_held) else 0
        if prev_held is not None and served != prev_held and served == raw:
            under_streak = 0
        prev_held = served
        # patience=1 is the stateless bucket, bit for bit
        assert plain.bucket(n_keep, m_total) == raw
    # hysteresis can only reduce transitions relative to the flapping bucket
    assert sticky.switches <= plain.switches


def test_sticky_bucket_defers_then_shrinks():
    sticky = StickyBucket(patience=3)
    assert sticky.bucket(100, 400) == 128
    for i in range(2):                      # two under-full ticks: still held
        assert sticky.bucket(10, 400) == 128
    assert sticky.bucket(10, 400) == 16     # third consecutive: shrink
    assert sticky.switches == 1             # (the initial 128 is not a switch)
    assert sticky.shrinks_deferred == 2
    assert sticky.bucket(200, 400) == 256   # growth is always immediate
    assert sticky.switches == 2


def test_sticky_bucket_idle_ticks_advance_shrink_streak():
    """All-skipped ticks count as under-full: after a quiet period of
    >= patience ticks the first active tick shrinks immediately (no stale
    oversized bucket survives a lull)."""
    sticky = StickyBucket(patience=3)
    assert sticky.bucket(100, 400) == 128
    for _ in range(3):
        sticky.observe_idle()               # nothing served, no transition
    assert sticky.switches == 0
    assert sticky.bucket(5, 400) == 8       # wake tick: shrinks right away
    # idle on a fresh instance is a no-op (nothing held to shrink)
    fresh = StickyBucket(patience=2)
    fresh.observe_idle()
    assert fresh.bucket(100, 400) == 128


# ---------------------------------------------------------------------------
# GateController invariants
# ---------------------------------------------------------------------------


@settings(max_examples=12)
@given(
    target=st.floats(0.05, 0.6),
    thr0=st.floats(1e-4, 1.0),
    seed=st.integers(0, 2**16),
)
def test_controller_bounded_step_and_clamp(target, thr0, seed):
    spec = _spec()
    cfg = GateControllerConfig(target=target)
    ctl = GateController(cfg, spec, thr0)
    rng = np.random.default_rng(seed)
    bh = math.ceil(spec.eff_h / spec.skip_block)
    bw = math.ceil(spec.eff_w / spec.skip_block)
    prev = ctl.threshold
    for t in range(24):
        mask = rng.random((bh, bw)) < rng.random()   # arbitrary plant
        keyframe = t % 7 == 0
        thr = ctl.observe(mask, keyframe=keyframe)
        assert cfg.min_threshold <= thr <= cfg.max_threshold
        # bounded actuation in log space
        assert abs(math.log(thr) - math.log(prev)) <= cfg.max_step + 1e-12
        if keyframe:
            assert thr == prev              # held-out tick never actuates
            assert ctl.history[-1]["observed"] is None
        prev = thr
    assert len(ctl.history) == 24


@settings(max_examples=6)
@given(seed=st.integers(0, 2**16))
def test_controller_energy_observation_matches_report(seed):
    """The hoisted-baseline energy observation equals the full report."""
    from repro.core import analysis

    spec = _spec()
    ctl = GateController(
        GateControllerConfig(target=0.2, metric="energy"), spec, 0.02
    )
    rng = np.random.default_rng(seed)
    bh = math.ceil(spec.eff_h / spec.skip_block)
    bw = math.ceil(spec.eff_w / spec.skip_block)
    mask = rng.random((bh, bw)) < 0.5
    rep = analysis.streaming_frontend_report(spec, [mask])
    assert ctl._observation(mask) == rep["energy_vs_dense"]


def test_controller_saturated_scene_no_windup():
    """A scene pinned at 0 kept windows must not wind up: once blocks appear
    again the threshold recovers within a few bounded steps."""
    spec = _spec()
    cfg = GateControllerConfig(target=0.15)
    ctl = GateController(cfg, spec, 0.02)
    bh = math.ceil(spec.eff_h / spec.skip_block)
    bw = math.ceil(spec.eff_w / spec.skip_block)
    empty = np.zeros((bh, bw), bool)
    for _ in range(50):
        ctl.observe(empty)
    # threshold driven to (near) the floor, integrator leaked + clamped
    assert ctl.threshold <= 0.02
    assert abs(ctl._integral) <= cfg.windup
    full = np.ones((bh, bw), bool)
    before = ctl.threshold
    ctl.observe(full)
    # the very next correction is bounded — no wound-up slam
    assert abs(math.log(ctl.threshold) - math.log(before)) <= cfg.max_step + 1e-12
