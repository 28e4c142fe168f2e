"""Streaming video frontend: temporal delta-gated region skipping with an
async double-buffered serving loop.

The paper's extreme-edge scenario is a sensor *watching a scene*, not a batch
oracle: §3.4.5's region skipping only pays off when the block keep/skip masks
are derived frame-to-frame.  This module closes that loop:

* :class:`StreamSession` holds per-stream state — the previous (effective)
  frame, the per-block change ages, and the registered configuration(s) it
  is programmed against.  Each frame steps a **temporal delta gate**
  (:func:`block_delta_mask`): per-``skip_block`` change detection against the
  previous frame, with hysteresis (a changed block stays live for a few
  frames, riding out sensor noise and slow motion) and periodic keyframe
  refresh (a full readout every ``keyframe_interval`` frames bounds drift).

* The resulting block mask is pushed *into the compute*: it becomes the
  per-window keep mask the fused kernel path compacts on (behind
  :class:`repro.fpca.CompiledFrontend`), so skipped windows never execute —
  the savings §3.4.5 accounts analytically become real executed-window
  savings.

* :class:`StreamServer` drives everything through an **async double-buffered
  loop**: jax dispatch is non-blocking, so the host-side work for frame
  ``t+1`` (window extraction geometry, delta gating, mask building) overlaps
  device compute for frame ``t``; a two-slot in-flight buffer (``depth``)
  bounds queue growth, and results are realised — and yielded — strictly in
  frame order.  Multiple streams (many cameras) registered on the same
  configuration fan into ONE device batch per tick, reusing the pipeline's
  shared executable cache and mesh sharding.

Adaptive control plane (the deployment loop on top):

* **Keep-fraction / energy servo** — pass a
  :class:`~repro.fpca.GateControllerConfig` and every stream gets its own
  :class:`~repro.serving.control.GateController`, closed-loop servoing its
  gate threshold against a kept-fraction / energy budget from the
  executed-window stats of each tick (EMA + bounded PI step in log space,
  anti-windup; keyframe ticks held out).

* **Multi-config fan-out** — a stream may be attached to *several*
  registered configurations sharing one spec
  (``add_stream(sid, ("edges", "blobs"))``); each tick gates the frame and
  serves every configuration through ONE channel-stacked fused call
  (:meth:`FPCAPipeline.run_config_batch` with a name list), yielding one
  :class:`StreamFrameResult` per (stream, config).

* **Per-config gate thresholds** — a multi-config stream may give each
  configuration its OWN delta gate (and its own servo):
  ``add_stream(sid, ("A", "B"), gate={"A": DeltaGateConfig(...), "B": ...})``.
  Each config keeps independent block ages / thresholds / controllers; the
  fused call executes the **union** of the per-config window masks (still
  one launch), and each config's channel slice is masked back to exactly its
  own keep decision — bit-identical to serving that config alone with that
  gate.

* **Sticky buckets** — the pipeline's ``bucket_patience`` keeps the
  compacted row bucket from flapping between power-of-two neighbours on
  busy scenes; the server mirrors the switch counters into
  :class:`StreamStats`.

Bit-exactness contract: kept-window activations are identical to a dense
readout (the dense reference in :mod:`repro.core.fpca_sim` is the oracle);
skipped windows read as exact zeros.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Any, Iterable, Iterator, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import analysis, gating, mapping
from repro.fpca import telemetry
from repro.fpca.program import (
    DeltaGateConfig,
    GateControllerConfig,
    ProgrammedModel,
)
from repro.models.heads import Detections
from repro.serving.control import GateController
from repro.serving.fpca_pipeline import FPCAPipeline

__all__ = [
    "DeltaGateConfig",
    "GateController",
    "GateControllerConfig",
    "StreamSession",
    "StreamFrameResult",
    "StreamServer",
    "block_delta",
    "block_delta_mask",
]

_USE_SERVER = object()   # add_stream sentinel: "inherit the server default"


def block_delta(
    prev_eff: np.ndarray, cur_eff: np.ndarray, spec: mapping.FPCASpec
) -> np.ndarray:
    """Mean absolute per-block change between two *effective* (binned)
    frames — the statistic every per-config threshold compares against.
    Jitted :mod:`repro.core.gating` numerics, the SAME jnp numerics the
    device-compiled segment executor inlines into its scan, so host and
    device gate decisions compare identical float32 bits."""
    kernels = gating.host_gate_kernels(spec)
    return np.asarray(
        kernels.delta(
            np.asarray(prev_eff, np.float32), np.asarray(cur_eff, np.float32)
        )
    )


def block_delta_mask(
    prev_eff: np.ndarray,
    cur_eff: np.ndarray,
    spec: mapping.FPCASpec,
    threshold: float,
) -> np.ndarray:
    """Per-block change detection between two *effective* (binned) frames.

    Returns the boolean ``(ceil(eff_h/B), ceil(eff_w/B))`` grid the periphery
    SRAM would hold (True = block changed beyond ``threshold`` mean absolute
    intensity) — the shape :func:`repro.core.mapping.active_window_mask`
    consumes.
    """
    return block_delta(prev_eff, cur_eff, spec) > threshold


class _GateState:
    """Delta-gate state for one configuration of one stream: its own gate
    knobs, block-age grid, servo controller and retained mask history.
    :func:`_step_gates` advances it as one row of its group's arrays, so
    its grids are views of those rows."""

    def __init__(
        self,
        name: str,
        gate: DeltaGateConfig,
        controller: GateController | None,
        block_shape: tuple[int, int],
        history: int,
    ):
        self.name = name
        self.gate = gate
        self.controller = controller
        self.age = np.full(block_shape, gate.hysteresis + 1, np.int64)
        self.last_keyframe = False
        self.last_block_mask: np.ndarray | None = None
        self.last_window_mask: np.ndarray | None = None
        # changed-block accounting for the event stream: ``last_changed`` is
        # the raw threshold comparison of the most recent gated tick (None
        # before the first delta), ``changed_total`` its running count —
        # EventTap packets must reconcile with it EXACTLY
        # (repro.serving.observe.assert_reconciled)
        self.last_changed: np.ndarray | None = None
        self.changed_total = 0
        # gate history for energy accounting, bounded so a long-running
        # stream does not leak (the report covers the retained window)
        self.block_masks: collections.deque[np.ndarray] = collections.deque(
            maxlen=history
        )

def _step_gates(
    spec: mapping.FPCASpec,
    sessions: Sequence["StreamSession"],
    deltas: np.ndarray,
    has_delta: np.ndarray,
    signed: np.ndarray | None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Advance the delta gates of gated ``sessions`` (all on ``spec``) by
    one frame in one host pass.

    ``deltas[j]`` is session ``j``'s block |Δ| grid, read where
    ``has_delta[j]`` (False on a stream's first frame), and ``signed[j]``
    its signed block-mean Δ (None when no session wants events).

    Each gate state (one per session, or one per config of a per-config
    session) is one row of ``(rows, bh, bw)`` arrays: the float32 threshold
    compare, ages, keyframe, keep and window grids are computed for all rows
    at once, and each state's attributes become views of its row.  A block
    is kept iff it changed within the last ``hysteresis + 1`` frames;
    keyframes (the first frame, then every ``keyframe_interval``) keep
    everything but do NOT reset the ages.  Per row, Python only points
    the state at its views and runs its servo, where it has one.  Returns, per session, the block keep mask
    ``(m, bh, bw)``, the window keep grid ``(m, h_o, w_o)`` and the kept
    window count ``(m,)`` — unions over a per-config session's states."""
    states = [st for s in sessions for st in s._states]
    per = [len(s._states) for s in sessions]
    frame_idx = np.array([s.frame_idx for s in sessions], np.int64)
    if len(states) != len(sessions):
        deltas = np.repeat(deltas, per, axis=0)
        has_delta = np.repeat(has_delta, per)
        frame_idx = np.repeat(frame_idx, per)
    gates = [st.gate for st in states]
    # float32 threshold on BOTH sides (numpy promotes the comparison
    # otherwise) — the same comparison the in-scan gate traces, so a delta
    # within 1 ulp of the threshold decides identically
    thr = np.array([g.threshold for g in gates], np.float32)
    hyst = np.array([g.hysteresis for g in gates], np.int64)
    interval = np.array([g.keyframe_interval for g in gates], np.int64)
    has = has_delta[:, None, None]
    changed = (deltas > thr[:, None, None]) & has
    ages = np.where(changed, 0, np.stack([st.age for st in states]) + has)
    keyframe = ~has_delta | (
        (interval > 0) & (frame_idx % np.maximum(interval, 1) == 0)
    )
    keep = keyframe[:, None, None] | (ages <= hyst[:, None, None])
    windows = mapping.active_window_masks(spec, keep)
    n_changed = np.count_nonzero(changed, axis=(1, 2))
    for j, st in enumerate(states):
        # changed-block accounting the event tap reconciles against
        st.last_changed = changed[j] if has_delta[j] else None
        st.changed_total += int(n_changed[j])
        st.age = ages[j]
        st.last_keyframe = bool(keyframe[j])
        st.last_block_mask = keep[j]
        st.block_masks.append(keep[j])
        st.last_window_mask = windows[j]
        if st.controller is not None:
            obs = (
                float(windows[j].mean())
                if st.controller.config.metric == "keep"
                else None
            )
            new_thr = st.controller.observe(
                keep[j], keyframe=bool(keyframe[j]), observation=obs
            )
            if new_thr != st.gate.threshold:
                st.gate = dataclasses.replace(st.gate, threshold=new_thr)
    if len(states) != len(sessions):
        starts = np.cumsum([0] + per[:-1])
        keep = np.logical_or.reduceat(keep, starts, axis=0)
        windows = np.logical_or.reduceat(windows, starts, axis=0)
    for j, session in enumerate(sessions):
        if session.want_events:
            # polarity source for the event tap: signed block-mean change
            session._last_signed = (
                signed[j] if signed is not None and has_delta[j] else None
            )
        session.frame_idx += 1
        session.last_window_mask = windows[j]
    return keep, windows, np.count_nonzero(windows, axis=(1, 2))


class _GroupState:
    """Every per-stream device state of one stream group, row ``j`` for
    ``sessions[j]``: ``prev``, the ``(n, eff_h, eff_w)`` float32 previous
    effective frames the delta gate compares against, and ``eff``, per
    model config the ``(n, h_o, w_o, C)`` effective activation maps the
    skip-aware head patches.  A session that has served a frame points at
    its row as ``session._row == (state, j)``; each tick's gate and head
    replace ``prev`` and ``eff[name]`` whole.  :func:`_regroup` builds
    one."""

    __slots__ = ("sessions", "prev", "eff")

    def __init__(self, sessions: list, prev: jax.Array, eff: dict):
        self.sessions = sessions
        self.prev = prev
        self.eff = eff


def _regroup(
    sessions: Sequence["StreamSession"],
    sources: Sequence[tuple | None] | None = None,
    *,
    release: bool = True,
) -> _GroupState:
    """Build one state for ``sessions`` and point each at its row.

    Row ``j`` comes from ``sources[j]`` (default: where ``sessions[j]``
    points now), a ``(state, row)``, or None for a stream that has served
    no frame.  Each field is one take from the sources' stacks behind a
    zero row, which a missing source, or a source state without a map for
    that model config, reads.  With ``release``, every stream that still
    reads a row of a source state but is not in ``sessions`` gets a
    one-row state of its own the same way, so none pins a stack its group
    has left."""
    if sources is None:
        sources = [s._row for s in sessions]
    olds = list({id(s[0]): s[0] for s in sources if s is not None}.values())
    eff_shapes = {
        name: a.shape[1:] for old in olds for name, a in old.eff.items()
    }

    def take(pick, shape):
        pools, base, at = [jnp.zeros((1, *shape), jnp.float32)], {}, 1
        for old in olds:
            a = pick(old)
            if a is not None:
                pools.append(a)
                base[id(old)] = at
                at += a.shape[0]
        idx = [
            0 if src is None or id(src[0]) not in base
            else base[id(src[0])] + src[1]
            for src in sources
        ]
        return jnp.concatenate(pools)[np.asarray(idx)]

    spec = sessions[0].spec
    state = _GroupState(
        list(sessions),
        take(lambda old: old.prev, (spec.eff_h, spec.eff_w)),
        {
            name: take(lambda old, name=name: old.eff.get(name), shape)
            for name, shape in eff_shapes.items()
        },
    )
    for j, s in enumerate(sessions):
        s._row = (state, j)
    if release:
        for old in olds:
            for i, t in enumerate(old.sessions):
                if t._row == (old, i):
                    _regroup([t], release=False)
    return state


def _state_of(
    sessions: Sequence["StreamSession"], stats: "StreamStats | None"
) -> _GroupState:
    """The state whose rows are exactly ``sessions``, in order: the one
    they all point at, as the last tick left it, or else one
    :func:`_regroup` builds.  ``stats`` (None: not counted) counts the
    gated rows as served resident or restacked."""
    row = sessions[0]._row
    state = None if row is None else row[0]
    resident = (
        state is not None
        and len(state.sessions) == len(sessions)
        and all(s._row == (state, j) for j, s in enumerate(sessions))
    )
    if not resident:
        state = _regroup(sessions)
    if stats is not None:
        n = sum(s.gating for s in sessions)
        if resident:
            stats.gate_rows_resident += n
        else:
            stats.gate_rows_restacked += n
    return state


def _gate_rows(
    state: _GroupState,
    rows: Sequence[int],
    frames: jax.Array,
    stats: "StreamStats | None",
) -> tuple[np.ndarray, np.ndarray | None]:
    """One vmapped gate dispatch (bit-identical to the solo kernel) for
    rows ``rows`` of ``state`` on their device ``frames``: the new
    effective frames replace those rows of ``state.prev`` on the device,
    so pixels never pass through the host, and only the ``(len(rows), bh,
    bw)`` block |Δ| grids come back, with the signed grids when one of the
    streams has an event tap (else None).  When every row is gated
    ``state.prev`` is stepped whole; a mixed group takes and scatters its
    gated rows.  ``stats`` (None: not billed) counts the bytes read
    back."""
    kernels = gating.host_gate_kernels(state.sessions[0].spec)
    whole = len(rows) == len(state.sessions)
    idx = None if whole else np.asarray(rows)
    prev = state.prev if whole else state.prev[idx]
    if any(state.sessions[i].want_events for i in rows):
        new, delta_d, signed_d = kernels.step_batch_signed(prev, frames)
        signed = np.asarray(signed_d)
    else:
        new, delta_d = kernels.step_batch(prev, frames)
        signed = None
    state.prev = new if whole else state.prev.at[idx].set(new)
    deltas = np.asarray(delta_d)
    if stats is not None:
        stats.d2h_bytes += deltas.nbytes + (
            0 if signed is None else signed.nbytes
        )
    return deltas, signed


class StreamSession:
    """Per-stream state: previous frame, block ages, programmed config(s).

    ``config`` may be one registered configuration name or a sequence of
    names sharing one spec (multi-config fan-out); :attr:`configs` always
    holds the normalised tuple and :attr:`config` the primary name.

    ``gate`` is one :class:`DeltaGateConfig` shared by every fanned-out
    configuration (the classic behaviour), or a mapping
    ``{config_name: DeltaGateConfig}`` giving each configuration its own
    independent gate (per-config block ages and thresholds); ``controller``
    follows the same shape with :class:`GateController` instances.  With
    controllers attached, every gated frame feeds the closed-loop threshold
    servo(s) and the per-config gates are re-derived for the next frame.

    ``stats`` (the owning server's :class:`StreamStats`) is billed the
    host↔device bytes of the gate dispatches this session makes itself.

    Its device state (the previous effective frame and, per model config,
    the effective activation map) is a row of a :class:`_GroupState`:
    its group's under :class:`StreamServer`, a one-row state of its own
    when stepped alone or served by a segment.  :attr:`_prev` reads the
    frame back on demand.
    """

    def __init__(
        self,
        stream_id: str,
        config: str | Sequence[str],
        spec: mapping.FPCASpec,
        gate: DeltaGateConfig | Mapping[str, DeltaGateConfig] | None,
        history: int = 512,
        controller: GateController | Mapping[str, GateController] | None = None,
        stats: "StreamStats | None" = None,
    ):
        self.stream_id = stream_id
        self.stats = stats
        self.configs: tuple[str, ...] = (
            (config,) if isinstance(config, str) else tuple(config)
        )
        if not self.configs:
            raise ValueError("need at least one config name")
        self.spec = spec
        self.per_config = isinstance(gate, Mapping) or isinstance(
            controller, Mapping
        )
        self.frame_idx = 0
        # (state, row) of the _GroupState holding this stream's device
        # state; None before the first frame
        self._row: tuple[_GroupState, int] | None = None
        bh = math.ceil(spec.eff_h / spec.skip_block)
        bw = math.ceil(spec.eff_w / spec.skip_block)
        self.last_window_mask: np.ndarray | None = None
        # device-resident carry threaded between compiled segment launches
        # (None until the stream first serves a segment)
        self._segment_state: Any | None = None
        # set by an attached EventTap: the gate dispatch then also computes
        # the SIGNED block-mean delta (the gate only needs |Δ|) and step()
        # retains it, so event polarity can be read after the previous frame
        # is overwritten
        self.want_events = False
        self._last_signed: np.ndarray | None = None

        def _pick(mapping_or_one: Any, name: str, kind: str) -> Any:
            if isinstance(mapping_or_one, Mapping):
                try:
                    return mapping_or_one[name]
                except KeyError:
                    raise KeyError(
                        f"per-config {kind} mapping is missing config "
                        f"{name!r} of stream {stream_id!r}"
                    ) from None
            return mapping_or_one

        self._states: list[_GateState] = []
        self._by_name: dict[str, _GateState] = {}
        # gating-off sessions still expose a (never-appended) mask history so
        # dense baselines keep the pre-redesign block_masks / energy_report
        # surface
        self._fallback_masks: collections.deque[np.ndarray] = collections.deque(
            maxlen=history
        )
        if gate is None and not self.per_config:
            self.gating = False
            return
        self.gating = True
        if self.per_config:
            for name in self.configs:
                g = _pick(gate, name, "gate")
                if g is None:
                    raise ValueError(
                        f"per-config gating needs a DeltaGateConfig for "
                        f"config {name!r}"
                    )
                st = _GateState(
                    name, g, _pick(controller, name, "controller"),
                    (bh, bw), history,
                )
                self._states.append(st)
                self._by_name[name] = st
        else:
            st = _GateState(
                self.configs[0], gate, controller, (bh, bw), history
            )
            self._states.append(st)
            for name in self.configs:
                self._by_name[name] = st

    # -- back-compat accessors (primary config's gate state) ----------------
    @property
    def config(self) -> str:
        """Primary configuration name (first of :attr:`configs`)."""
        return self.configs[0]

    @property
    def _primary(self) -> _GateState | None:
        return self._states[0] if self._states else None

    @property
    def gate(self) -> DeltaGateConfig | None:
        """Primary config's gate (None = gating off / dense)."""
        st = self._primary
        return st.gate if st is not None else None

    @property
    def controller(self) -> GateController | None:
        st = self._primary
        return st.controller if st is not None else None

    @property
    def last_keyframe(self) -> bool:
        st = self._primary
        return st.last_keyframe if st is not None else False

    @property
    def block_masks(self) -> collections.deque:
        st = self._primary
        return st.block_masks if st is not None else self._fallback_masks

    def state_for(self, config: str) -> _GateState | None:
        """This config's gate state (shared state unless per-config)."""
        return self._by_name.get(config)

    @property
    def _prev(self) -> np.ndarray | None:
        """The previous effective frame, read back to the host from this
        stream's row (None before the first frame, and for a dense
        stream)."""
        if self._row is None or not self.gating:
            return None
        state, j = self._row
        return np.asarray(state.prev[j], np.float32)

    def step(self, frame: Any) -> np.ndarray | None:
        """Advance one frame, gated alone; returns the block keep mask
        (None = dense).

        With controllers attached, the masks also feed the threshold
        servo(s), so the NEXT frame gates with the servoed threshold(s).
        With per-config gates, the returned mask (and
        :attr:`last_window_mask`) is the **union** over configs — what the
        fused call must execute; each config's own decision is on its
        :meth:`state_for` entry.

        The frame (a host or device array) goes to the device and is gated
        against this stream's one-row state (:func:`_gate_rows`, the
        dispatch :class:`StreamServer` makes for a whole group; a first
        frame only stores its effective frame there); only the block |Δ|
        grid (and the signed grid for :attr:`want_events`) comes back, and
        the gate decision is :func:`_step_gates` on this one stream.
        """
        if not self.gating:
            self.frame_idx += 1
            return None
        if not isinstance(frame, jax.Array):
            frame = np.asarray(frame, np.float32)
            if self.stats is not None:
                self.stats.h2d_bytes += frame.nbytes
        frame = jnp.asarray(frame)
        has_delta = np.array([self.frame_idx > 0])
        state = _state_of([self], self.stats)
        if has_delta[0]:
            deltas, signed = _gate_rows(state, [0], frame[None], self.stats)
        else:
            # a first frame has nothing to compare: keep its effective frame
            state.prev = gating.host_gate_kernels(self.spec).eff(frame)[None]
            deltas = np.zeros((1, *self._primary.age.shape), np.float32)
            signed = None
        keep, _, _ = _step_gates(self.spec, [self], deltas, has_delta, signed)
        return keep[0]

    def absorb_segment(self, seg) -> None:
        """Fold one finished device-compiled segment into this session.

        A segment serves K ticks from one launch; the host session never saw
        those frames, so its mirror of the gate state (previous frame, block
        ages, frame index, mask history, servo) is rebuilt here from the
        segment's realised bookkeeping — after this call, per-tick
        :meth:`step` serving continues bit-identically from where the
        segment stopped, and :meth:`energy_report` /
        :meth:`GateController.converged_tick` audits cover the in-segment
        ticks as if they had been served one by one.  The servo applies ONE
        bounded actuation at the boundary
        (:meth:`GateController.observe_segment`).  The segment's device
        carry (previous effective frame and, for a model, effective map)
        becomes this stream's one-row state, without a host copy.
        """
        if self.per_config:
            raise NotImplementedError(
                "compiled segments serve one gate per stream; per-config "
                "fan-out streams must use per-tick serving"
            )
        carry = seg.state
        eff = {} if carry.eff is None else {self.config: carry.eff[None]}
        _regroup([self], [(_GroupState([self], carry.prev_eff[None], eff), 0)])
        ticks = seg.ticks
        if not seg.gated or not self.gating:
            if seg.gated != self.gating:
                raise ValueError(
                    "segment gating does not match this session "
                    f"(segment gated={seg.gated}, session gating={self.gating})"
                )
            self.frame_idx += ticks
            return
        st = self._primary
        masks = [np.asarray(m) for m in seg.block_masks[:ticks]]
        for m in masks:
            st.block_masks.append(m)
        if ticks:
            st.last_keyframe = bool(seg.keyframes[ticks - 1])
            st.last_block_mask = masks[-1]
            window = mapping.active_window_mask(self.spec, masks[-1])
            st.last_window_mask = window
            self.last_window_mask = window
        st.age = np.asarray(seg.state.age, np.int64)
        self.frame_idx = int(seg.state.frame_idx)
        if st.controller is not None and ticks:
            obs = None
            if st.controller.config.metric == "keep":
                h_o, w_o = mapping.output_dims(self.spec)
                obs = [
                    float(k) / float(h_o * w_o)
                    for k in seg.kept_windows[:ticks]
                ]
            new_thr = st.controller.observe_segment(
                masks,
                keyframes=seg.keyframes[:ticks],
                observations=obs,
            )
            if new_thr != st.gate.threshold:
                st.gate = dataclasses.replace(st.gate, threshold=new_thr)

    def energy_report(
        self,
        const: analysis.FrontendConstants | None = None,
        config: str | None = None,
    ) -> dict:
        """Executed-window energy/cycle accounting over the retained gate
        history (the last ``history`` frames).  ``config`` selects one
        fanned-out configuration's gate history (default: the primary's —
        which under shared gating is *the* history)."""
        if config is not None:
            st = self._by_name.get(config)
            if st is None:
                raise KeyError(f"unknown config {config!r} for this session")
            masks = st.block_masks
        else:
            masks = self.block_masks
        return analysis.streaming_frontend_report(
            self.spec, list(masks), const or analysis.FrontendConstants()
        )


@dataclasses.dataclass
class StreamFrameResult:
    """One (stream, config)'s activations for one tick of the serving loop.

    Single-config streams yield one result per tick; a multi-config stream
    yields one per fanned-out configuration (same ``frame_idx``; per-config
    ``counts``, and per-config ``block_mask`` / ``kept_windows`` when the
    stream uses per-config gates), distinguished by ``config``.

    Streams attached to a **model** configuration
    (:class:`repro.fpca.ProgrammedModel`) also carry per-tick class
    ``logits``: the skip-aware head path patches this tick's kept-window
    activations into the stream's previous effective activation map and runs
    the digital head on the patched map, so even a mostly-skipped tick
    yields a class decision (an all-skipped tick reproduces the previous
    logits exactly).
    """

    stream_id: str
    frame_idx: int
    counts: np.ndarray              # (h_o, w_o, c_o) SS-ADC counts
    block_mask: np.ndarray | None   # gate output (None = dense readout)
    kept_windows: int
    total_windows: int
    config: str = ""                # configuration these counts belong to
    logits: np.ndarray | None = None  # (n_classes,) logits, or the raw
    #                                 # (gh, gw, n_classes + 4) detection map
    detections: Any | None = None   # heads.Detections — detection configs
    events: Any | None = None       # events.EventPacket — event-tap streams

    @property
    def kept_fraction(self) -> float:
        return self.kept_windows / max(self.total_windows, 1)

    @property
    def predicted_class(self) -> int | None:
        """Argmax class of a classifier tick; None for dense-counts-only
        ticks AND for detection ticks (whose logits are per-cell maps —
        use :attr:`detections`)."""
        if self.logits is None or np.ndim(self.logits) != 1:
            return None
        return int(np.argmax(self.logits))


class StreamStats(telemetry.StatsView):
    """Fleet-level serving counters, registry-backed (see
    :class:`repro.fpca.telemetry.StatsView`).

    ``windows_kept`` counts logical kept windows (pre-bucket-pad);
    ``launches_skipped`` counts all-skipped ticks (per-tick serving
    short-circuits AND zero-kept ticks inside device-compiled segments);
    ``bucket_switches`` / ``bucket_shrinks_deferred`` mirror the sticky
    bucket hysteresis; ``segments`` / ``segment_ticks`` cover compiled
    segment launches; ``fused_head_calls`` counts shared-head fusion
    launches (several same-signature model configs served by ONE batched
    head pass — see :meth:`StreamServer._model_head_pass`);
    ``serve_seconds`` accumulates wall-clock time spent
    in the serving loop (dispatch + realisation) — the denominator
    :func:`repro.serving.observe.fleet_report` derives fps from.
    ``h2d_bytes`` / ``d2h_bytes`` count the bytes the per-tick path moves
    across the host↔device boundary: ``nbytes`` of every host array that
    enters a device call (gate inputs, frames, keep grids) and of every
    device array it realises on the host (gate results, counts, logits).
    ``gate_rows_resident`` / ``gate_rows_restacked`` split the gated
    stream-ticks by where their device state came from: the group's
    :class:`_GroupState` as the last tick left it (a one-row state of the
    stream's own, gated alone), or one rebuilt first (a first frame, a
    change of the group's members or their order, a state replaced by a
    segment).

    The server deliberately does NOT parent-chain into the pipeline's
    stats: it is a scoped observer of a *shared* pipeline (other callers
    may drive the same pipeline), so the bucket/skip counters are
    delta-mirrored around each launch instead.
    """

    _PREFIX = "fpca_stream"
    _FIELDS = (
        "ticks",
        "frames",
        "windows_total",
        "windows_kept",
        "launches_skipped",
        "bucket_switches",
        "bucket_shrinks_deferred",
        "segments",
        "segment_ticks",
        "fused_head_calls",
        "serve_seconds",
        "h2d_bytes",
        "d2h_bytes",
        "gate_rows_resident",
        "gate_rows_restacked",
    )


class StreamServer:
    """Async double-buffered multi-stream driver over :class:`FPCAPipeline`.

    A thin fleet-orchestration layer: gating and batching happen here, every
    fused launch goes through the pipeline's per-signature
    :class:`repro.fpca.CompiledFrontend` handles (single-camera workloads
    can skip this class entirely and use
    :meth:`repro.fpca.CompiledFrontend.stream`).

    Args:
      pipeline: the serving pipeline whose registered configurations,
        executable cache and mesh sharding this server reuses.
      gate: delta-gate configuration applied to every stream; pass
        ``gating=False`` for a dense baseline server (no skipping — what the
        benchmark compares against).  With a ``controller``, this is only the
        *initial* gate — each stream's threshold is then servoed
        independently.  Both can be overridden per stream (and per config)
        in :meth:`add_stream`.
      controller: optional :class:`GateControllerConfig`; every stream added
        afterwards gets its own :class:`GateController` closed-loop servoing
        the gate threshold against the configured budget.
      depth: maximum in-flight ticks.  ``2`` is classic double buffering:
        while the device chews on tick ``t``, the host gates and batches tick
        ``t+1``; results for ``t`` are realised only when ``t+2`` is about to
        dispatch.
    """

    def __init__(
        self,
        pipeline: FPCAPipeline,
        gate: DeltaGateConfig = DeltaGateConfig(),
        *,
        depth: int = 2,
        gating: bool = True,
        controller: GateControllerConfig | None = None,
    ):
        if depth < 1:
            raise ValueError("depth must be >= 1")
        self.pipeline = pipeline
        self.gate = gate if gating else None
        self.controller = controller if gating else None
        self.depth = depth
        self.sessions: dict[str, StreamSession] = {}
        self.event_taps: dict[str, Any] = {}
        self.stats = StreamStats()
        # prebuilt span label dicts (one per server / per stream); only an
        # enabled-telemetry tick builds its own, to add the tick index
        self._span_fields = {"server": self.stats._labels["instance"]}
        self._seg_fields: dict[str, dict] = {}

    def add_stream(
        self,
        stream_id: str,
        config: str | Sequence[str],
        *,
        gate: Any = _USE_SERVER,
        controller: Any = _USE_SERVER,
        events: bool = False,
    ) -> StreamSession:
        """Attach a camera stream to registered pipeline configuration(s).

        ``events=True`` attaches an :class:`repro.serving.events.EventTap`:
        every served tick additionally emits the delta gate's changed blocks
        as an address-event packet on ``StreamFrameResult.events`` (requires
        a gated, shared-gate stream).

        A sequence of names fans the stream out to several programmed
        configurations sharing one spec: each tick is gated and served
        through one channel-stacked fused call, yielding one
        :class:`StreamFrameResult` per configuration.

        ``gate`` / ``controller`` override the server-wide defaults for this
        stream: a :class:`DeltaGateConfig` /
        :class:`GateControllerConfig` replaces the default, an explicit
        ``None`` disables gating / servoing for this stream (a per-stream
        dense baseline even on a gated server), and omitting the argument
        inherits the server default.  Passing a mapping
        ``{config_name: DeltaGateConfig}`` (and / or
        ``{config_name: GateControllerConfig}``) gives each fanned-out
        configuration its own independent gate state and servo — the fused
        call then executes the union of the per-config masks and each
        config's results are masked back to its own keep decision.
        """
        if stream_id in self.sessions:
            raise ValueError(f"stream {stream_id!r} already attached")
        names = (config,) if isinstance(config, str) else tuple(config)
        cfgs = []
        for n in names:
            cfg = self.pipeline._configs.get(n)
            if cfg is None:
                raise KeyError(f"unknown config {n!r}")
            cfgs.append(cfg)
        spec = cfgs[0].spec
        base = cfgs[0].program.fanout_signature()
        for cfg in cfgs[1:]:
            # one stacked call per tick serves one adc/enc/circuit epilogue:
            # require full compile-signature compatibility, not just a
            # shared spec (a 3-bit-ADC config stacked with an 8-bit one
            # would silently serve the wrong saturation)
            if cfg.program.fanout_signature() != base:
                raise ValueError(
                    f"multi-config stream needs a shared spec and compile "
                    f"signature (adc/enc/circuit): config {cfg.name!r} "
                    f"differs from {cfgs[0].name!r}"
                )
        eff_gate = self.gate if gate is _USE_SERVER else gate
        eff_ctl = self.controller if controller is _USE_SERVER else controller
        per_config = isinstance(eff_gate, Mapping) or isinstance(eff_ctl, Mapping)

        def _controller_for(g: DeltaGateConfig, name: str) -> GateController | None:
            if eff_ctl is None or g is None:
                return None
            conf = (
                eff_ctl[name]
                if isinstance(eff_ctl, Mapping)
                else eff_ctl
            )
            if not conf:
                return None
            return GateController(
                conf, spec, g.threshold, name=f"{stream_id}/{name}"
            )

        if per_config:
            if eff_gate is None:
                raise ValueError(
                    "per-config controllers need gating enabled (pass gate=)"
                )
            for kind, m in (("gate", eff_gate), ("controller", eff_ctl)):
                if isinstance(m, Mapping):
                    missing = [n for n in names if n not in m]
                    if missing:
                        raise KeyError(
                            f"per-config {kind} mapping is missing config "
                            f"{missing[0]!r} of stream {stream_id!r}"
                        )
            gate_map = {
                n: (eff_gate[n] if isinstance(eff_gate, Mapping) else eff_gate)
                for n in names
            }
            ctl_map = {n: _controller_for(gate_map[n], n) for n in names}
            session = StreamSession(
                stream_id, names, spec, gate_map, controller=ctl_map,
                stats=self.stats,
            )
        else:
            ctl = (
                _controller_for(eff_gate, names[0])
                if eff_gate is not None
                else None
            )
            session = StreamSession(
                stream_id, names, spec, eff_gate, controller=ctl,
                stats=self.stats,
            )
        self.sessions[stream_id] = session
        self._seg_fields[stream_id] = {"stream": stream_id}
        if events:
            from repro.serving.events import EventTap

            try:
                self.event_taps[stream_id] = EventTap(session)
            except Exception:
                # leave no half-attached stream behind: the session was
                # registered above, but an events=True caller asked for a
                # contract this stream cannot honour
                del self.sessions[stream_id]
                del self._seg_fields[stream_id]
                raise
        return session

    # -- serving loop --------------------------------------------------------
    def _dispatch(self, frames: Mapping[str, Any]) -> list[dict]:
        """Host side of one tick: gate every stream, fan streams into one
        batch per configuration group, dispatch without blocking.

        Each group's work runs under the telemetry spans ``stage``,
        ``gate`` (``gate_readback``, then ``gate_streams``, inside it),
        ``frontend`` and ``head``, in that order: the group's frames go to
        the device once, and the gate and the frontend both read that
        copy."""
        per_group: dict[tuple[str, ...], list[tuple[StreamSession, np.ndarray]]] = {}
        for stream_id, frame in frames.items():
            session = self.sessions.get(stream_id)
            if session is None:
                raise KeyError(f"unknown stream {stream_id!r}")
            per_group.setdefault(session.configs, []).append(
                (session, np.asarray(frame, np.float32))
            )
        pstats = self.pipeline.stats
        before = (
            pstats.bucket_switches,
            pstats.bucket_shrinks_deferred,
            pstats.launches_skipped,
            pstats.h2d_bytes,
        )
        launches: list[dict] = []
        for configs, members in per_group.items():
            spec = members[0][0].spec
            h_o, w_o = mapping.output_dims(spec)
            gated = any(session.gating for session, _ in members)
            with telemetry.span("stage"):
                host_images = np.stack([frame for _, frame in members])
                images = jnp.asarray(host_images)
                self.stats.h2d_bytes += host_images.nbytes
            with telemetry.span("gate"):
                entries, keep = self._gate_group(
                    members, images, spec, h_o, w_o, gated
                )
            with telemetry.span("frontend"):
                counts = self.pipeline.run_config_batch(
                    configs[0] if len(configs) == 1 else list(configs),
                    images,
                    keep,
                )
            slices = (
                self.pipeline.config_channel_slices(configs)
                if len(configs) > 1
                else [(configs[0], None, None)]
            )
            launch = {"counts": counts, "entries": entries, "slices": slices}
            with telemetry.span("head"):
                self._model_head_pass(launch, members, keep, h_o, w_o)
            launches.append(launch)
        self.stats.bucket_switches += pstats.bucket_switches - before[0]
        self.stats.bucket_shrinks_deferred += pstats.bucket_shrinks_deferred - before[1]
        self.stats.launches_skipped += pstats.launches_skipped - before[2]
        self.stats.h2d_bytes += pstats.h2d_bytes - before[3]
        return launches

    def _gate_group(
        self, members: list, images: jax.Array, spec: mapping.FPCASpec,
        h_o: int, w_o: int, gated: bool,
    ) -> tuple[list[dict], np.ndarray | None]:
        """Gate every stream of one configuration group on its staged
        device frames ``images``; returns the group's result entries and
        (``gated``) its window keep grids ``(n, h_o, w_o)``.

        Under the ``gate`` span: ``gate_readback`` is the group's device
        state (:func:`_state_of`), the batched gate dispatch and its grid
        read-back, ``gate_streams`` the one host pass over the group's gate
        states (:func:`_step_gates`), the servos, entries and keep grid."""
        total = h_o * w_o
        with telemetry.span("gate_readback"):
            _state_of([session for session, _ in members], self.stats)
            batch = self._gate_batch(members, images, spec) if gated else None
        with telemetry.span("gate_streams"):
            frame_idx = [session.frame_idx for session, _ in members]
            blocks: list[np.ndarray | None] = [None] * len(members)
            kept = [total] * len(members)
            keep = None
            if batch is not None:
                rows, deltas, has_delta, signed = batch
                row_keep, windows, row_kept = _step_gates(
                    spec, [members[i][0] for i in rows], deltas, has_delta,
                    signed,
                )
                for j, i in enumerate(rows):
                    blocks[i] = row_keep[j]
                    kept[i] = int(row_kept[j])
                if len(rows) == len(members):
                    keep = windows
                else:
                    keep = np.ones((len(members), h_o, w_o), bool)
                    keep[rows] = windows
            entries = []
            for i, (session, frame) in enumerate(members):
                if not session.gating:
                    session.step(frame)
                entry = {
                    "stream_id": session.stream_id,
                    "frame_idx": frame_idx[i],
                    "block_mask": blocks[i],
                    "kept": kept[i],
                    "total": total,
                }
                if session.per_config:
                    entry["per_config"] = {
                        st.name: (
                            st.last_block_mask,
                            int(np.count_nonzero(st.last_window_mask)),
                            st.last_window_mask,
                        )
                        for st in session._states
                    }
                tap = self.event_taps.get(session.stream_id)
                if tap is not None:
                    # emit this tick's address-event packet from the gate
                    # state the group pass just wrote (same changed array
                    # the gate counted — the reconciliation contract)
                    entry["events"] = tap.observe_tick(frame_idx[i])
                entries.append(entry)
            self.stats.frames += len(members)
            self.stats.windows_total += total * len(members)
            self.stats.windows_kept += sum(kept)
        return entries, keep

    def _gate_batch(
        self, members: list, images: jax.Array, spec: mapping.FPCASpec
    ) -> tuple[list[int], np.ndarray, np.ndarray, np.ndarray | None]:
        """Fleet-batched gate of a group's gated streams: ONE dispatch on
        the staged frames and the group state's previous effective frames
        (:func:`_gate_rows`), so the per-tick host cost stays flat as the
        fleet grows.  Returns, for :func:`_step_gates`, the gated members'
        rows, the ``(n, bh, bw)`` |Δ| grids, which of them hold a delta (a
        first frame's row, against the zero placeholder, does not) and the
        signed grids (None without an event tap)."""
        rows = [i for i, (s, _) in enumerate(members) if s.gating]
        frames = images if len(rows) == len(members) else images[np.asarray(rows)]
        has_delta = np.array([members[i][0].frame_idx > 0 for i in rows])
        state = members[0][0]._row[0]
        deltas, signed = _gate_rows(state, rows, frames, self.stats)
        return rows, deltas, has_delta, signed

    def _model_head_pass(
        self, launch: dict, members: list, keep: np.ndarray | None,
        h_o: int, w_o: int,
    ) -> None:
        """Skip-aware digital head for model configurations of one group.

        For every :class:`repro.fpca.ProgrammedModel` slice of the fused
        launch: patch the kept windows (``keep``, the gate's window grids,
        or a per-config stream's own config's) into the group state's
        ``(n, h_o, w_o, C)`` effective maps (zeros before any stream of the
        group has served the config) and dispatch the head on the
        patched maps — ONE batched, non-blocking call, so the
        double-buffered overlap is preserved; the patched maps replace the
        state's whole.  An all-skipped tick patches nothing and reproduces
        the previous logits exactly.

        Model configs of one launch binding the SAME model signature (zoo
        archs sharing a head graph, A/B weight variants) share ONE vmapped
        head pass over their concatenated (config, stream) rows, each row
        binding its own config's head parameters.  The patched-head math is
        row-independent, so the result is bit-identical to one call per
        config (pinned in the zoo tests).
        """
        counts = launch["counts"]
        state = members[0][0]._row[0]
        n = len(members)
        base = np.ones((n, h_o, w_o), bool) if keep is None else keep
        per_config = [j for j, (s, _) in enumerate(members) if s.per_config]
        logits_by_config: dict[str, Any] = {}
        detect_by_config: dict[str, int] = {}
        groups: dict[tuple, list[tuple]] = {}
        for name, lo, hi in launch["slices"]:
            cfg = self.pipeline._configs[name]
            if not isinstance(cfg, ProgrammedModel):
                continue
            groups.setdefault(cfg.model.signature(), []).append(
                (name, counts if lo is None else counts[..., lo:hi], cfg)
            )
            if name not in state.eff:       # no stream has served it yet
                state.eff[name] = jnp.zeros(
                    (n, h_o, w_o, cfg.out_channels), jnp.float32
                )
            if cfg.model.detect_classes is not None:
                detect_by_config[name] = cfg.model.detect_classes

        def keep_for(name):
            if not per_config:
                return base
            grid = base.copy()
            for j in per_config:
                grid[j] = members[j][0].state_for(name).last_window_mask
            return grid

        for group in groups.values():
            handle = self.pipeline.model_handle_for(group[0][2].model)
            grid = np.concatenate([keep_for(name) for name, _, _ in group])
            self.stats.h2d_bytes += grid.nbytes
            if len(group) == 1:
                name, sliced, cfg = group[0]
                logits_by_config[name], state.eff[name] = handle.patched_logits(
                    sliced, state.eff[name], grid, head_params=cfg.head_params
                )
                continue
            # config-major rows: [g*n, (g+1)*n) are config g's streams,
            # each row binding g's head params
            hp_stack = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs),
                *[cfg.head_params for _, _, cfg in group for _ in range(n)],
            )
            logits, eff = handle.fused_patched_logits(
                hp_stack,
                jnp.concatenate([sliced for _, sliced, _ in group]),
                jnp.concatenate([state.eff[name] for name, _, _ in group]),
                grid,
            )
            self.stats.fused_head_calls += 1
            for g, (name, _, _) in enumerate(group):
                logits_by_config[name] = logits[g * n:(g + 1) * n]
                state.eff[name] = eff[g * n:(g + 1) * n]
        if logits_by_config:
            launch["logits"] = logits_by_config
        if detect_by_config:
            launch["detect"] = detect_by_config

    def _finalize(self, launches: list[dict]) -> list[StreamFrameResult]:
        """Device side of one tick: realise the batch (blocks) and unpack.

        Per-config-gated streams executed the union mask; here each config's
        channel slice is masked back to exactly its own keep decision (kept
        windows are bit-identical to solo serving — row-independent math —
        and windows the config skipped read as exact zeros)."""
        results: list[StreamFrameResult] = []
        for launch in launches:
            counts = np.asarray(launch["counts"])     # blocks until ready
            logits_np = {
                name: np.asarray(lg)
                for name, lg in launch.get("logits", {}).items()
            }
            self.stats.d2h_bytes += counts.nbytes + sum(
                lg.nbytes for lg in logits_np.values()
            )
            detect = launch.get("detect", {})
            for row, e in enumerate(launch["entries"]):
                per_config = e.get("per_config")
                for idx, (name, lo, hi) in enumerate(launch["slices"]):
                    sliced = (
                        counts[row] if lo is None else counts[row, ..., lo:hi]
                    )
                    block, kept = e["block_mask"], e["kept"]
                    if per_config is not None and name in per_config:
                        block, kept, window = per_config[name]
                        sliced = sliced * window[..., None].astype(sliced.dtype)
                    lg = logits_np.get(name)
                    det = None
                    if lg is not None and name in detect:
                        det = Detections.from_raw(lg[row], detect[name])
                    results.append(
                        StreamFrameResult(
                            stream_id=e["stream_id"],
                            frame_idx=e["frame_idx"],
                            counts=sliced,
                            block_mask=block,
                            kept_windows=kept,
                            total_windows=e["total"],
                            config=name,
                            logits=None if lg is None else lg[row],
                            detections=det,
                            # one packet per (stream, tick): attach to the
                            # first fanned-out config's result only
                            events=e.get("events") if idx == 0 else None,
                        )
                    )
        return results

    def run(
        self, ticks: Iterable[Mapping[str, Any]]
    ) -> Iterator[list[StreamFrameResult]]:
        """Serve a stream of ticks; yields one result list per tick, in order.

        Each tick maps ``stream_id -> frame``.  Up to ``depth`` ticks are in
        flight at once: dispatch is non-blocking (jax async), so tick ``t``'s
        device compute overlaps tick ``t+1``'s host gating/batching; results
        are realised oldest-first, preserving frame order per stream.
        """
        inflight: collections.deque[tuple[int, list[dict]]] = collections.deque()
        for frames in ticks:
            # single-exit wall-clock billing: the dispatch half of the tick
            # is accumulated exactly once even when the gate/batch path
            # raises, so fps_wall never loses (or double-counts) time
            t0 = time.perf_counter()
            try:
                tick = int(self.stats.ticks)
                with telemetry.span("serve_tick", self._tick_fields(tick)):
                    inflight.append((tick, self._dispatch(frames)))
                self.stats.ticks += 1
            finally:
                self.stats.serve_seconds += time.perf_counter() - t0
            while len(inflight) > self.depth:
                yield self._finalize_timed(*inflight.popleft())
        while inflight:
            yield self._finalize_timed(*inflight.popleft())

    def _tick_fields(self, tick: int) -> dict | None:
        """Span fields naming one tick: built only while a telemetry
        session is on, so a disabled tick allocates no dict."""
        if not telemetry.enabled():
            return None
        return {**self._span_fields, "tick": tick}

    def _finalize_timed(
        self, tick: int, launches: list[dict]
    ) -> list[StreamFrameResult]:
        """Realise one in-flight tick (the ``realise`` span, its ``tick``
        that of the ``serve_tick`` it realises), billing its wall time
        exactly once (``try/finally`` — a device error mid-realisation
        still accounts the seconds already spent)."""
        t0 = time.perf_counter()
        try:
            with telemetry.span("realise", self._tick_fields(tick)):
                return self._finalize(launches)
        finally:
            self.stats.serve_seconds += time.perf_counter() - t0

    def serve(self, stream_id: str, frames: Iterable[Any]) -> Iterator[StreamFrameResult]:
        """Single-stream convenience wrapper around :meth:`run`.

        Yields one result per tick for a single-config stream; a
        multi-config stream yields its per-config results back to back
        (same ``frame_idx``, distinguished by ``result.config``).
        """
        for results in self.run({stream_id: f} for f in frames):
            yield from results

    # -- device-compiled segment mode ----------------------------------------
    def run_segment(
        self,
        stream_id: str,
        frames: Any,
        *,
        m_bucket: int | None = None,
        early_exit: int | None = None,
    ) -> list[StreamFrameResult]:
        """Serve a ``(K, H, W, c_i)`` frame stack of one stream as ONE
        device-compiled segment (``jax.lax.scan`` tick loop — see
        :meth:`repro.fpca.CompiledFrontend.run_segment`).

        The session's gate runs *inside* the scan (bit-identical decisions —
        the host mirror is rebuilt from the segment's realised bookkeeping by
        :meth:`StreamSession.absorb_segment`, so per-tick :meth:`run` serving
        and segment serving interleave freely on one stream).  The threshold
        servo applies one bounded step at the segment boundary; with a
        ``"keep"``-metric controller the next segment's compacted row bucket
        defaults to the finished segment's realised kept counts.  Returns the
        per-tick results in frame order (fewer than K with ``early_exit`` —
        feed the unserved tail to the next call).  Single-config streams
        only; per-config fan-out must use per-tick :meth:`run`.
        """
        # same single-exit billing contract as run(): an exception inside
        # the segment launch still accounts the wall time already spent
        t0 = time.perf_counter()
        try:
            with telemetry.span("serve_segment", self._seg_fields.get(stream_id)):
                return self._run_segment_inner(
                    stream_id, frames, m_bucket=m_bucket, early_exit=early_exit
                )
        finally:
            self.stats.serve_seconds += time.perf_counter() - t0

    def _run_segment_inner(
        self,
        stream_id: str,
        frames: Any,
        *,
        m_bucket: int | None = None,
        early_exit: int | None = None,
    ) -> list[StreamFrameResult]:
        session = self.sessions.get(stream_id)
        if session is None:
            raise KeyError(f"unknown stream {stream_id!r}")
        if session.per_config or len(session.configs) > 1:
            raise NotImplementedError(
                "segment mode serves single-config streams; multi-config "
                "fan-out must use per-tick run()"
            )
        name = session.config
        state = session._segment_state
        if state is not None and int(state.frame_idx) != session.frame_idx:
            # per-tick serving advanced the stream since the last segment;
            # the device carry is stale — rebuild it from the host mirror
            state = None
        if state is None and session.frame_idx > 0:
            state = self._state_from_session(session, name)
        start_idx = session.frame_idx
        tap = self.event_taps.get(stream_id)
        # event reconstruction inputs, captured BEFORE the launch mutates
        # them: the effective frame carried INTO the segment and the
        # threshold the scan traces (the servo actuates only at the boundary,
        # inside absorb_segment)
        if tap is not None:
            prev_eff_in = (
                np.asarray(state.prev_eff, np.float32)
                if state is not None and bool(state.has_prev)
                else None
            )
            thr_in = float(session.gate.threshold)
        pstats = self.pipeline.stats
        before = (pstats.launches_skipped, pstats.segments, pstats.segment_ticks)
        seg = self.pipeline.run_config_segment(
            name,
            frames,
            state=state,
            gate=session.gate if session.gating else None,
            m_bucket=m_bucket,
            early_exit=early_exit,
        )
        session._segment_state = seg.state
        cfg = self.pipeline._configs[name]
        is_model = isinstance(cfg, ProgrammedModel)
        session.absorb_segment(seg)
        # a boundary servo step retunes the threshold for the NEXT segment —
        # the traced gate args pick it up without recompiling
        self.stats.launches_skipped += pstats.launches_skipped - before[0]
        self.stats.segments += pstats.segments - before[1]
        self.stats.segment_ticks += pstats.segment_ticks - before[2]
        ticks = seg.ticks
        h_o, w_o = mapping.output_dims(session.spec)
        total = h_o * w_o
        self.stats.ticks += ticks
        self.stats.frames += ticks
        self.stats.windows_total += ticks * total
        self.stats.windows_kept += int(seg.kept_windows[:ticks].sum())
        counts = np.asarray(seg.counts)        # blocks until the scan is done
        logits = None if seg.logits is None else np.asarray(seg.logits)
        packets = None
        if tap is not None:
            # the scan never materialises per-tick gate internals on the
            # host; re-derive the served ticks' event packets through the
            # same gating kernels the scan traced (bit-identical decisions —
            # the per-tick-vs-segment differential test pins it) and fold
            # them into tap + gate accounting in lock-step
            from repro.serving.events import segment_events

            packets = segment_events(
                session.spec,
                np.asarray(frames, np.float32)[:ticks],
                prev_eff_in,
                thr_in,
                stream_id,
                start_idx,
            )
            tap.absorb_packets(packets)
        detect_classes = cfg.model.detect_classes if is_model else None
        results = []
        for t in range(ticks):
            lg = None if logits is None else logits[t]
            results.append(
                StreamFrameResult(
                    stream_id=stream_id,
                    frame_idx=start_idx + t,
                    counts=counts[t],
                    block_mask=(
                        np.asarray(seg.block_masks[t]) if seg.gated else None
                    ),
                    kept_windows=int(seg.kept_windows[t]),
                    total_windows=total,
                    config=name,
                    logits=lg,
                    detections=(
                        Detections.from_raw(lg, detect_classes)
                        if lg is not None and detect_classes is not None
                        else None
                    ),
                    events=None if packets is None else packets[t],
                )
            )
        return results

    def _state_from_session(self, session: StreamSession, name: str):
        """Segment carry seeded from the stream's row of its group state
        (device arrays, no host copy) and its host gate state, so a stream
        that served ticks through :meth:`run` can continue in segment
        mode."""
        from repro.fpca.executable import SegmentState

        spec = session.spec
        group, j = session._row
        st = session._primary
        bh = math.ceil(spec.eff_h / spec.skip_block)
        bw = math.ceil(spec.eff_w / spec.skip_block)
        hyst = session.gate.hysteresis if session.gate is not None else 0
        state = SegmentState(
            has_prev=session.gating,
            prev_eff=group.prev[j],
            age=(
                st.age if st is not None
                else np.full((bh, bw), hyst + 1, np.int64)
            ),
            frame_idx=session.frame_idx,
        )
        cfg = self.pipeline._configs[name]
        if isinstance(cfg, ProgrammedModel):
            state.eff = group.eff[name][j]
            # the scan's quiet-tick branch replays the carried logits; the
            # host path recomputes head(eff) each tick, which is the same bits
            handle = self.pipeline.model_handle_for(cfg.model)
            state.logits = handle.head_logits(
                state.eff[None], head_params=cfg.head_params
            )[0]
        return state

    def serve_segments(
        self,
        stream_id: str,
        frames: Iterable[Any],
        *,
        segment_length: int = 16,
        m_bucket: int | None = None,
        early_exit: int | None = None,
        on_segment: Any = None,
    ) -> Iterator[StreamFrameResult]:
        """Segment-mode twin of :meth:`serve`: buffers the frame iterable
        into ``segment_length`` chunks and serves each as one compiled
        segment, yielding per-tick results in frame order.

        With ``early_exit`` a segment may serve fewer than ``segment_length``
        ticks; the unserved tail is carried into the next chunk.  The final
        partial chunk compiles one executable for its own length — steady
        streams see exactly one compile per distinct chunk length.

        ``on_segment`` (callable of the segment's result list) fires at
        every segment boundary, after the servo's boundary actuation —
        where :class:`repro.serving.fleet.FleetController` re-solves the
        fleet budget split.
        """
        if segment_length < 1:
            raise ValueError("segment_length must be >= 1")
        buf: list[np.ndarray] = []
        for f in frames:
            buf.append(np.asarray(f, np.float32))
            if len(buf) >= segment_length:
                results = self.run_segment(
                    stream_id,
                    np.stack(buf[:segment_length]),
                    m_bucket=m_bucket,
                    early_exit=early_exit,
                )
                if on_segment is not None:
                    on_segment(results)
                yield from results
                buf = buf[len(results):]
        while buf:
            results = self.run_segment(
                stream_id,
                np.stack(buf),
                m_bucket=m_bucket,
                early_exit=early_exit,
            )
            if on_segment is not None:
                on_segment(results)
            yield from results
            buf = buf[len(results):]
