"""Differential parity harness for device-compiled streaming segments.

``CompiledFrontend.run_segment`` rolls K streaming ticks — delta gate,
hysteresis ages, keyframe cadence, kept-window compaction, skip-aware head —
into ONE ``jax.lax.scan`` launch.  That moves five host-side state machines
onto the device, so the contract pinned here is strict: the scan segment must
be **bit-identical, tick for tick**, to the existing per-tick Python loop,
across backends (reference / basis / interpret-pallas), dense and gated,
through zero-kept ticks, keyframe boundaries, compacted-bucket edges, early
exit, mid-stream ``reprogram()``, and host↔device mode interleaving.

Lanes:

* ``@pytest.mark.segment`` — the CI api-surface fast lane: tiny spec, K=4.
* ``@pytest.mark.slow``    — the full K=48 grid across all three backends,
  bucket edges, early exit, and the property sweeps.

Property tests (via ``_hypothesis_compat``) check the scan carry state
machine (block keep grid, keyframe flags, block ages, frame index, previous
logits) against ``StreamSession``'s host-side transitions for arbitrary
frame sequences and gate configs — the gate knobs enter the scan traced, so
the whole sweep shares one compiled executable.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

import repro.fpca as fpca
from _hypothesis_compat import given, settings, st
from repro.core import gating
from repro.core.mapping import FPCASpec, output_dims
from repro.fpca.cache import ExecutableCache
from repro.fpca.executable import CompiledFrontend, CompiledModel
from repro.serving.fpca_pipeline import FPCAPipeline
from repro.serving.streaming import StreamServer, StreamSession

H = W = 24
C_O = 3
GATE = fpca.DeltaGateConfig(threshold=0.02, hysteresis=1, keyframe_interval=4)
BACKENDS = ("reference", "basis", "pallas")   # pallas runs interpret=True


def _spec() -> FPCASpec:
    return FPCASpec(image_h=H, image_w=W, out_channels=C_O, kernel=5, stride=5)


def _kernel(seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(C_O, 5, 5, 3)) * 0.2).astype(np.float32)


def _frames(k: int, seed: int = 0, static: tuple[int, ...] = ()) -> np.ndarray:
    """A random scene; indices in ``static`` repeat their predecessor frame
    (zero block delta — the all-skipped regime)."""
    rng = np.random.default_rng(seed)
    frames = rng.uniform(0, 1, size=(k, H, W, 3)).astype(np.float32)
    for i in static:
        frames[i] = frames[i - 1]
    return frames


def _scene(k: int, seed: int = 0) -> np.ndarray:
    """Moving-blob scene with static stretches and a busy stretch — covers
    zero-kept ticks, partial keeps, and keyframe-interval crossings."""
    rng = np.random.default_rng(seed)
    frames = np.empty((k, H, W, 3), np.float32)
    base = rng.uniform(0, 1, size=(H, W, 3)).astype(np.float32)
    for t in range(k):
        f = base.copy()
        if t % 7 < 4:                     # moving blob 4 of every 7 ticks
            c = (t * 3) % (H - 6)
            f[c : c + 6, c : c + 6] += 0.5
        frames[t] = np.clip(f, 0, 1)
    # two fully-static stretches (frame repeated verbatim)
    for i in range(5, min(8, k)):
        frames[i] = frames[4]
    for i in range(k - 3, k):
        if i > 0:
            frames[i] = frames[k - 4]
    return frames


_HANDLES: dict[tuple, CompiledFrontend] = {}


def _fe(bucket_model, backend: str, gate=GATE) -> CompiledFrontend:
    key = (backend, gate)
    fe = _HANDLES.get(key)
    if fe is None:
        fe = fpca.compile(
            fpca.FPCAProgram(spec=_spec(), gate=gate),
            backend=backend, weights=_kernel(), model=bucket_model,
            interpret=True,
        )
        _HANDLES[key] = fe
    return fe


def _model_handle(bucket_model, backend: str = "basis") -> CompiledModel:
    key = (backend, "model")
    md = _HANDLES.get(key)
    if md is None:
        mp = fpca.FPCAModelProgram(
            frontend=fpca.FPCAProgram(spec=_spec(), gate=GATE),
            head=(fpca.DenseSpec(8, activation="relu"), fpca.DenseSpec(3)),
        )
        md = fpca.compile(
            mp, backend=backend, weights=_kernel(), model=bucket_model,
            head_params=mp.init_head(jax.random.PRNGKey(0)), interpret=True,
        )
        _HANDLES[key] = md
    return md  # type: ignore[return-value]


def _assert_segment_matches_stream(fe, frames, seg, gate=GATE) -> None:
    """Tick-for-tick bit-identity of one segment against the per-tick loop."""
    results = list(fe.stream(frames, gate=gate, controller=None))
    assert seg.ticks == len(results) == frames.shape[0]
    for t, r in enumerate(results):
        np.testing.assert_array_equal(
            np.asarray(seg.counts)[t], r.counts, err_msg=f"counts tick {t}"
        )
        assert int(seg.kept_windows[t]) == r.kept_windows, f"kept tick {t}"
        if gate is not None:
            np.testing.assert_array_equal(
                seg.block_masks[t], r.block_mask, err_msg=f"mask tick {t}"
            )
        if r.logits is not None:
            np.testing.assert_array_equal(
                np.asarray(seg.logits)[t], r.logits, err_msg=f"logits tick {t}"
            )


# ---------------------------------------------------------------------------
# fast lane (CI api-surface job: -m segment)
# ---------------------------------------------------------------------------


@pytest.mark.segment
@pytest.mark.parametrize("backend", ["reference", "basis"])
def test_segment_parity_fast(bucket_model, backend):
    """K=4 scan segment, gated, bit-identical to the per-tick loop."""
    fe = _fe(bucket_model, backend)
    frames = _frames(4, static=(2,))
    seg = fe.run_segment(frames, length=4)
    _assert_segment_matches_stream(fe, frames, seg)
    assert seg.gated and seg.length == 4 and seg.first_frame_idx == 0
    assert bool(seg.keyframes[0])           # first tick keyframes
    assert int(seg.state.frame_idx) == 4


@pytest.mark.segment
def test_segment_dense_fast(bucket_model):
    fe = _fe(bucket_model, "basis")
    frames = _frames(4)
    seg = fe.run_segment(frames, gate=None)
    _assert_segment_matches_stream(fe, frames, seg, gate=None)
    assert not seg.gated
    assert (seg.kept_windows == output_dims(_spec())[0] ** 2).all()


@pytest.mark.segment
def test_segment_chaining_fast(bucket_model):
    """Two chained K=2 segments == one K=4 segment, bit for bit."""
    fe = _fe(bucket_model, "basis")
    frames = _frames(4, static=(2,))
    whole = fe.run_segment(frames)
    s1 = fe.run_segment(frames[:2])
    s2 = fe.run_segment(frames[2:], state=s1.state)
    assert s2.first_frame_idx == 2
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(s1.counts), np.asarray(s2.counts)]),
        np.asarray(whole.counts),
    )
    np.testing.assert_array_equal(
        np.concatenate([s1.kept_windows, s2.kept_windows]), whole.kept_windows
    )


@pytest.mark.segment
def test_segment_model_fast(bucket_model):
    """Model segment: in-scan skip-aware head, logits every tick."""
    md = _model_handle(bucket_model)
    frames = _frames(4, static=(2, 3))
    seg = md.run_segment(frames)
    assert seg.logits is not None and np.asarray(seg.logits).shape == (4, 3)
    _assert_segment_matches_stream(md, frames, seg)
    # the all-skipped tick reproduced the carried previous logits exactly
    zero_ticks = np.flatnonzero(seg.kept_windows == 0)
    assert zero_ticks.size >= 1
    for t in zero_ticks:
        np.testing.assert_array_equal(
            np.asarray(seg.logits)[t], np.asarray(seg.logits)[t - 1]
        )


# ---------------------------------------------------------------------------
# full grid (slow lane)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("backend", BACKENDS)
def test_segment_parity_k48(bucket_model, backend):
    """The acceptance contract: K=48, gated, bit-identical per tick on every
    backend, through keyframe boundaries and zero-kept stretches."""
    fe = _fe(bucket_model, backend)
    frames = _scene(48)
    seg = fe.run_segment(frames, length=48)
    _assert_segment_matches_stream(fe, frames, seg)
    assert (seg.kept_windows == 0).any()        # the scene went quiet
    assert seg.keyframes[: 48 : GATE.keyframe_interval].all()


@pytest.mark.slow
@pytest.mark.parametrize("backend", BACKENDS)
def test_segment_parity_dense_k48(bucket_model, backend):
    fe = _fe(bucket_model, backend)
    frames = _scene(48, seed=1)
    seg = fe.run_segment(frames, gate=None)
    _assert_segment_matches_stream(fe, frames, seg, gate=None)


@pytest.mark.slow
@pytest.mark.parametrize("m_bucket", [1, 2, 3, 15, 16])
def test_segment_bucket_edges(bucket_model, m_bucket):
    """Compacted-bucket edges (1, 2, pow2±1, M): any static bucket serves
    bit-identically — overflowing ticks fall back to the masked-dense branch
    inside the scan."""
    fe = _fe(bucket_model, "basis")
    frames = _scene(16, seed=2)
    ref = fe.run_segment(frames)                 # masked-dense (bucket M)
    seg = fe.run_segment(frames, m_bucket=m_bucket)
    np.testing.assert_array_equal(np.asarray(seg.counts), np.asarray(ref.counts))
    np.testing.assert_array_equal(seg.kept_windows, ref.kept_windows)
    # rows accounting reflects the bucket: kept<=bucket ticks bill the
    # bucket, overflows bill M, zero-kept ticks bill nothing
    M = output_dims(_spec())[0] ** 2
    kept = seg.kept_windows
    expect = np.where(kept == 0, 0, np.where(kept > m_bucket, M, m_bucket))
    np.testing.assert_array_equal(seg.rows_executed, expect)


@pytest.mark.slow
def test_segment_kept_extremes(bucket_model):
    """n_keep = 0 and n_keep = M inside one gated segment.

    The threshold is tiny-but-positive, not 0.0: XLA may rematerialise the
    effective frame into the carry store and the delta reduction with
    different fusions (a ~1e-8 wobble), so exactly-repeated frames compare
    "changed" against a zero threshold — identically on host and device,
    which is the parity contract, but not the extreme this test wants."""
    gate = fpca.DeltaGateConfig(threshold=1e-6, hysteresis=0,
                                keyframe_interval=0)
    fe = _fe(bucket_model, "basis", gate=gate)
    frames = _frames(6, seed=3, static=(2, 3))
    seg = fe.run_segment(frames)
    M = output_dims(_spec())[0] ** 2
    # any real change keeps everything; repeated frames keep nothing
    assert set(int(v) for v in np.unique(seg.kept_windows)) == {0, M}
    _assert_segment_matches_stream(fe, frames, seg, gate=gate)


@pytest.mark.slow
def test_segment_reprogram_between_segments(bucket_model):
    """reprogram() between segments: zero recompiles, and the chained output
    equals a per-tick host loop that switches kernels at the same tick."""
    fe = fpca.compile(
        fpca.FPCAProgram(spec=_spec(), gate=GATE), backend="basis",
        weights=_kernel(0), model=bucket_model, interpret=True,
    )
    frames = _scene(12, seed=4)
    k2 = _kernel(7)
    s1 = fe.run_segment(frames[:6])
    misses = fe.cache_info().misses
    fe.reprogram(k2)
    s2 = fe.run_segment(frames[6:], state=s1.state)
    assert fe.cache_info().misses == misses      # ZERO recompiles

    # host oracle: per-tick loop, same kernel switch at tick 6
    host = fpca.compile(
        fpca.FPCAProgram(spec=_spec(), gate=GATE), backend="basis",
        weights=_kernel(0), model=bucket_model, interpret=True,
    )
    it = host.stream(frames, depth=1)
    expect = [next(it).counts for _ in range(6)]
    host.reprogram(k2)
    expect += [r.counts for r in it]
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(s1.counts), np.asarray(s2.counts)]),
        np.stack(expect),
    )


@pytest.mark.slow
@pytest.mark.parametrize("backend", BACKENDS)
def test_segment_early_exit(bucket_model, backend):
    """while_loop variant: a quiescent scene stops the segment early; the
    served prefix is bit-identical, and resuming serves the rest exactly."""
    gate = fpca.DeltaGateConfig(threshold=0.02, hysteresis=0,
                                keyframe_interval=0)
    fe = _fe(bucket_model, backend, gate=gate)
    frames = _frames(10, seed=5)
    frames[4:] = frames[3]                       # scene freezes at tick 4
    ref = fe.run_segment(frames, gate=gate)      # uninterrupted scan
    seg = fe.run_segment(frames, gate=gate, early_exit=2)
    assert seg.ticks < 10
    assert (seg.kept_windows[seg.ticks - 2 : seg.ticks] == 0).all()
    np.testing.assert_array_equal(
        np.asarray(seg.counts)[: seg.ticks],
        np.asarray(ref.counts)[: seg.ticks],
    )
    # resume with the remaining frames: the continuation is bit-identical
    rest = fe.run_segment(frames[seg.ticks :], state=seg.state, gate=gate)
    np.testing.assert_array_equal(
        np.asarray(rest.counts), np.asarray(ref.counts)[seg.ticks :]
    )
    np.testing.assert_array_equal(
        rest.kept_windows, ref.kept_windows[seg.ticks :]
    )


@pytest.mark.slow
def test_segment_length_and_shape_validation(bucket_model):
    fe = _fe(bucket_model, "basis")
    frames = _frames(4)
    with pytest.raises(ValueError, match="length"):
        fe.run_segment(frames, length=8)
    with pytest.raises(ValueError, match="frame stack"):
        fe.run_segment(frames[0])
    with pytest.raises(ValueError, match="early_exit"):
        fe.run_segment(frames, gate=None, early_exit=2)
    with pytest.raises(ValueError, match="patience"):
        fe.run_segment(frames, early_exit=0)


# ---------------------------------------------------------------------------
# property tests: scan carry vs StreamSession host transitions
# ---------------------------------------------------------------------------


@pytest.mark.slow
@settings(max_examples=10, deadline=None)
@given(
    threshold=st.floats(0.001, 0.2),
    hysteresis=st.integers(0, 3),
    keyframe_interval=st.integers(0, 5),
    seed=st.integers(0, 2**16),
)
def test_scan_gate_matches_session_transitions(
    bucket_model, threshold, hysteresis, keyframe_interval, seed
):
    """The scan's gate state machine (keep grid, keyframes, ages, frame
    index) matches StreamSession.step for arbitrary frame sequences and gate
    configs.  Gate knobs enter the scan traced, so the whole sweep shares
    ONE compiled executable."""
    gate = fpca.DeltaGateConfig(
        threshold=threshold, hysteresis=hysteresis,
        keyframe_interval=keyframe_interval,
    )
    fe = _fe(bucket_model, "reference")          # gate=GATE handle; gate
    frames = _frames(6, seed=seed, static=(2, 4, 5))
    seg = fe.run_segment(frames, gate=gate)      # passed per call (traced)
    session = StreamSession("s", "cfg", _spec(), gate)
    for t in range(6):
        keep = session.step(frames[t])
        st_ = session._primary
        np.testing.assert_array_equal(
            seg.block_masks[t], keep, err_msg=f"keep grid tick {t}"
        )
        assert bool(seg.keyframes[t]) == st_.last_keyframe, f"keyframe {t}"
        assert int(seg.kept_windows[t]) == int(st_.last_window_mask.sum())
    np.testing.assert_array_equal(
        np.asarray(seg.state.age, np.int64), session._primary.age
    )
    assert int(seg.state.frame_idx) == session.frame_idx
    np.testing.assert_array_equal(
        np.asarray(seg.state.prev_eff), session._prev
    )


@pytest.mark.slow
@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**16), hysteresis=st.integers(0, 2))
def test_scan_model_carry_matches_host_logits(bucket_model, seed, hysteresis):
    """Previous-logits carry: model segments reproduce the host skip-aware
    head trajectory (quiet ticks replay carried logits) bit-exactly."""
    gate = fpca.DeltaGateConfig(
        threshold=0.02, hysteresis=hysteresis, keyframe_interval=3
    )
    md = _model_handle(bucket_model)
    frames = _frames(6, seed=seed, static=(3, 4))
    seg = md.run_segment(frames, gate=gate)
    host = [
        np.asarray(r.logits)
        for r in md.stream(frames, gate=gate, controller=None)
    ]
    np.testing.assert_array_equal(np.asarray(seg.logits), np.stack(host))


# ---------------------------------------------------------------------------
# ExecutableCache coexistence (regression: no cross-eviction thrash)
# ---------------------------------------------------------------------------


def test_cache_holds_segment_and_batch_executables(bucket_model):
    """Segment, frontend, and model executables for ONE program coexist in a
    shared cache without evicting each other; reprogram() after run_segment
    still compiles nothing."""
    cache = ExecutableCache(16)
    md = fpca.compile(
        fpca.FPCAModelProgram(
            frontend=fpca.FPCAProgram(spec=_spec(), gate=GATE),
            head=(fpca.DenseSpec(8, activation="relu"), fpca.DenseSpec(3)),
        ),
        backend="basis", weights=_kernel(), model=bucket_model,
        head_params=None, interpret=True, cache=cache,
    )
    mp = md.model_program
    md.reprogram(head_params=mp.init_head(jax.random.PRNGKey(0)))
    frames = _frames(4, static=(2,))
    images = frames[:2]

    md.run(images)                               # batched model executable
    md.run_segment(frames)                       # segment executable
    md.run_frontend_weighted(                    # frontend-only executable
        md.kernel, md.bn_offset, images
    )
    info_warm = md.cache_info()
    assert info_warm.evictions == 0

    # a second pass over all three paths hits the warm cache only
    md.run(images)
    md.run_segment(frames)
    md.run_frontend_weighted(md.kernel, md.bn_offset, images)
    info = md.cache_info()
    assert info.misses == info_warm.misses       # no cross-eviction thrash
    assert info.evictions == 0

    # reprogram after run_segment: still zero recompiles on EVERY path
    md.reprogram(_kernel(9))
    md.run(images)
    md.run_segment(frames)
    assert md.cache_info().misses == info_warm.misses


# ---------------------------------------------------------------------------
# segment-aware stats and serving-layer integration
# ---------------------------------------------------------------------------


def _pipeline(bucket_model) -> FPCAPipeline:
    pipe = FPCAPipeline(bucket_model, backend="basis", interpret=True)
    pipe.register("cam", fpca.FPCAProgram(spec=_spec(), gate=GATE), _kernel())
    return pipe


def test_stats_are_segment_aware(bucket_model):
    """K ticks from one launch must report like K per-tick launches:
    launches_skipped counts in-scan zero-kept ticks, windows accounting
    covers every tick, and segments/segment_ticks record the rollup."""
    frames = _frames(6, static=(2, 3, 4))
    srv_tick = StreamServer(_pipeline(bucket_model), GATE)
    srv_tick.add_stream("cam0", "cam")
    list(srv_tick.serve("cam0", frames))

    srv_seg = StreamServer(_pipeline(bucket_model), GATE)
    srv_seg.add_stream("cam0", "cam")
    srv_seg.run_segment("cam0", frames)

    a, b = srv_seg.stats, srv_tick.stats
    assert a.ticks == b.ticks == 6
    assert a.frames == b.frames
    assert a.windows_total == b.windows_total
    assert a.windows_kept == b.windows_kept
    assert a.launches_skipped == b.launches_skipped > 0
    assert a.segments == 1 and a.segment_ticks == 6
    assert b.segments == 0 and b.segment_ticks == 0
    ps = srv_seg.pipeline.stats
    assert ps.segments == 1 and ps.segment_ticks == 6
    assert ps.launches_skipped == a.launches_skipped


def test_session_energy_report_covers_segment_ticks(bucket_model):
    """streaming_frontend_report stays honest: the session's retained mask
    history after a segment equals the per-tick history."""
    frames = _frames(6, static=(2, 3))
    srv_seg = StreamServer(_pipeline(bucket_model), GATE)
    srv_seg.add_stream("cam0", "cam")
    srv_seg.run_segment("cam0", frames)
    srv_tick = StreamServer(_pipeline(bucket_model), GATE)
    srv_tick.add_stream("cam0", "cam")
    list(srv_tick.serve("cam0", frames))
    rep_seg = srv_seg.sessions["cam0"].energy_report()
    rep_tick = srv_tick.sessions["cam0"].energy_report()
    assert rep_seg == rep_tick


def test_server_segment_mode_matches_per_tick(bucket_model):
    frames = _frames(8, static=(2, 3, 6))
    srv_tick = StreamServer(_pipeline(bucket_model), GATE)
    srv_tick.add_stream("cam0", "cam")
    ref = list(srv_tick.serve("cam0", frames))
    srv_seg = StreamServer(_pipeline(bucket_model), GATE)
    srv_seg.add_stream("cam0", "cam")
    got = list(srv_seg.serve_segments("cam0", frames, segment_length=4))
    assert len(got) == len(ref)
    for a, b in zip(got, ref):
        assert a.frame_idx == b.frame_idx
        assert a.kept_windows == b.kept_windows
        np.testing.assert_array_equal(a.counts, b.counts)
        np.testing.assert_array_equal(a.block_mask, b.block_mask)


def _model_pipeline(bucket_model) -> FPCAPipeline:
    mp = _model_handle(bucket_model).model_program
    pipe = FPCAPipeline(bucket_model, backend="basis", interpret=True)
    pipe.register("cam", mp, _kernel(), head_params=mp.init_head(
        jax.random.PRNGKey(0)))
    return pipe


@pytest.mark.parametrize("make_pipeline", [_pipeline, _model_pipeline],
                         ids=["frontend", "model"])
def test_server_interleaves_tick_and_segment_modes(bucket_model, make_pipeline):
    """tick -> segment -> segment -> tick on ONE stream stays bit-identical
    to pure per-tick serving (absorb_segment rebuilds the host mirror; the
    first segment seeds its carry, model head included, from the per-tick
    state, and the second runs on the donated carry of the first)."""
    frames = _frames(11, static=(2, 5))
    srv_ref = StreamServer(make_pipeline(bucket_model), GATE)
    srv_ref.add_stream("cam0", "cam")
    ref = list(srv_ref.serve("cam0", frames))
    srv = StreamServer(make_pipeline(bucket_model), GATE)
    srv.add_stream("cam0", "cam")
    got = list(srv.serve("cam0", frames[:3]))
    got += srv.run_segment("cam0", frames[3:6])
    got += srv.run_segment("cam0", frames[6:8])
    got += list(srv.serve("cam0", frames[8:]))
    assert [r.frame_idx for r in got] == [r.frame_idx for r in ref]
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(a.counts, b.counts)
        assert a.kept_windows == b.kept_windows
        if b.logits is not None:
            np.testing.assert_array_equal(a.logits, b.logits)


def test_boundary_servo_steps_once_per_segment(bucket_model):
    """The threshold is constant inside a segment (traced gate args) and the
    servo applies one bounded actuation at the boundary; history still
    records every in-segment tick."""
    ctl = fpca.GateControllerConfig(target=0.3)
    srv = StreamServer(_pipeline(bucket_model), GATE, controller=ctl)
    session = srv.add_stream("cam0", "cam")
    thr0 = session.gate.threshold
    srv.run_segment("cam0", _frames(6, seed=11))
    c = session.controller
    assert c is not None and len(c.history) == 6
    in_segment = {h["threshold"] for h in c.history}
    assert in_segment == {thr0}                  # constant inside the segment
    assert session.gate.threshold != thr0        # one boundary actuation
    # the actuation is bounded exactly like a single per-tick step
    import math
    assert abs(math.log(session.gate.threshold) - math.log(thr0)) <= (
        ctl.max_step + 1e-12
    )


def test_boundary_servo_zero_tick_segment_is_a_no_op():
    """A zero-tick segment (early-exit fired before serving anything) made
    no observation, so the boundary servo must neither fold the stale EMA
    nor spend an actuation — the threshold stays bit-exactly where the last
    real observation left it."""
    from repro.serving.control import GateController

    spec = _spec()
    ctl = GateController(
        fpca.GateControllerConfig(target=0.3), spec, GATE.threshold
    )
    # seed real state: one observed segment moves the threshold
    bh = -(-spec.eff_h // spec.skip_block)
    bw = -(-spec.eff_w // spec.skip_block)
    masks = np.ones((3, bh, bw), bool)
    thr1 = ctl.observe_segment(masks, keyframes=[True, False, False])
    ema1, hist1, tick1 = ctl.ema, len(ctl.history), ctl._tick
    assert thr1 != GATE.threshold
    # the zero-tick boundary: identical threshold, EMA, history, tick count
    thr2 = ctl.observe_segment(np.zeros((0, bh, bw), bool))
    assert thr2 == thr1 == ctl.threshold
    assert ctl.ema == ema1
    assert len(ctl.history) == hist1 and ctl._tick == tick1


def test_segment_bucket_suggestion_threads_to_next_segment(bucket_model):
    """The finished segment sizes the next one's compacted row bucket
    (pow2 of the max informative kept count); serving with it stays
    bit-identical."""
    fe = _fe(bucket_model, "basis")
    frames = _scene(12, seed=6)
    s1 = fe.run_segment(frames[:6])
    assert s1.state.suggested_bucket is not None
    assert s1.state.suggested_bucket >= 1
    ref = fe.run_segment(frames[6:], state=dataclasses.replace(
        s1.state, suggested_bucket=None), donate=False)
    s2 = fe.run_segment(frames[6:], state=s1.state)   # uses the suggestion
    np.testing.assert_array_equal(
        np.asarray(s2.counts), np.asarray(ref.counts)
    )


def test_segment_donated_state_is_consumed(bucket_model):
    """Segments donate their carry by default (on every platform): the
    state passed in is dead afterwards, and running from it again fails
    with a named error instead of reading a deleted buffer."""
    fe = _fe(bucket_model, "basis")
    frames = _scene(8, seed=6)
    s1 = fe.run_segment(frames[:4])
    kept = fe.run_segment(frames[4:], state=s1.state, donate=False)
    s2 = fe.run_segment(frames[4:], state=s1.state)
    np.testing.assert_array_equal(np.asarray(s2.counts), np.asarray(kept.counts))
    assert s1.state.prev_eff.is_deleted()
    with pytest.raises(ValueError, match="donated"):
        fe.run_segment(frames[4:], state=s1.state)
    # the returned state threads on
    assert fe.run_segment(frames[4:], state=s2.state).first_frame_idx == 8


def test_frontend_stats_count_segments(bucket_model):
    fe = fpca.compile(
        fpca.FPCAProgram(spec=_spec(), gate=GATE), backend="basis",
        weights=_kernel(), model=bucket_model, interpret=True,
    )
    frames = _frames(5, static=(2, 3))
    seg = fe.run_segment(frames)
    M = output_dims(_spec())[0] ** 2
    assert fe.stats.segments == 1
    assert fe.stats.segment_ticks == 5
    assert fe.stats.windows_total == 5 * M
    assert fe.stats.windows_executed == int(seg.rows_executed.sum())
    assert fe.stats.launches_skipped == int((seg.kept_windows == 0).sum()) > 0


# ---------------------------------------------------------------------------
# shared gate numerics (the bit-parity foundation)
# ---------------------------------------------------------------------------


def test_host_gate_kernels_are_single_source():
    """The host loop's gate numerics ARE the scan's (one jnp implementation;
    the fused host step kernel returns the same bits as the split calls)."""
    spec = _spec()
    kernels = gating.host_gate_kernels(spec)
    rng = np.random.default_rng(0)
    a = rng.uniform(0, 1, (H, W, 3)).astype(np.float32)
    b = rng.uniform(0, 1, (H, W, 3)).astype(np.float32)
    ea = np.asarray(kernels.eff(a))
    eb, delta_fused = kernels.step(ea, b)
    np.testing.assert_array_equal(np.asarray(eb), np.asarray(kernels.eff(b)))
    # the fused step is deterministic (same bits every call) — this is what
    # the parity contract rests on; against the *split* kernels XLA may fuse
    # the reductions differently, so only closeness is promised there
    eb2, delta2 = kernels.step(ea, b)
    np.testing.assert_array_equal(np.asarray(delta_fused), np.asarray(delta2))
    np.testing.assert_array_equal(np.asarray(eb), np.asarray(eb2))
    np.testing.assert_allclose(
        np.asarray(delta_fused),
        np.asarray(kernels.delta(ea, np.asarray(eb))),
        rtol=0, atol=1e-6,
    )


def test_host_gate_step_batch_matches_solo_bitwise():
    """The vmapped fleet kernel gates every stream of a group in ONE
    dispatch; per row it must return the same float32 bits as the solo
    fused step — a 1-ulp drift would flip keep/skip decisions and break
    the parity contract for batched fleet serving."""
    for spec in (_spec(), FPCASpec(image_h=H, image_w=18, out_channels=C_O,
                                   kernel=3, stride=3, binning=2)):
        kernels = gating.host_gate_kernels(spec)
        rng = np.random.default_rng(1)
        n = 5
        prevs = rng.uniform(
            0, 1, (n, spec.eff_h, spec.eff_w)
        ).astype(np.float32)
        frames = rng.uniform(
            0, 1, (n, spec.image_h, spec.image_w, 3)
        ).astype(np.float32)
        curs, deltas = kernels.step_batch(prevs, frames)
        for i in range(n):
            cur_i, delta_i = kernels.step(prevs[i], frames[i])
            np.testing.assert_array_equal(
                np.asarray(curs)[i], np.asarray(cur_i)
            )
            np.testing.assert_array_equal(
                np.asarray(deltas)[i], np.asarray(delta_i)
            )
