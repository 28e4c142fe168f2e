"""Neuromorphic event streams: the delta gate's per-block changes as a
first-class sensor output.

The temporal delta gate (:mod:`repro.serving.streaming`) already computes,
every tick, which coarse blocks of the effective frame changed beyond a
threshold — exactly the statistic an event camera / P2M pixel array emits as
address-event spikes.  :class:`EventTap` surfaces it as a per-tick
:class:`EventPacket` stream: block coordinates, polarity (sign of the mean
block change) and a wall-clock timestamp, with its own registry-backed
:class:`EventStats` accounting (labeled ``arch="events"`` so
``fleet_report()``'s workload table and the Prometheus render break the
event lane out next to classifier / detection traffic).

Two emission paths, one numerics contract:

* **per-tick** — :meth:`EventTap.observe_tick` reads the gate state the
  session just stepped (the *same* ``changed`` array the gate counted, plus
  a signed block-mean delta computed before the previous frame is
  overwritten), so the tap's event counts and the gate's changed-block
  accounting can never drift (:func:`repro.serving.observe.assert_reconciled`
  asserts exact equality);
* **segment** — a device-compiled segment never materialises per-tick gate
  internals on the host, so :func:`segment_events` *re-derives* them from
  the frames and the carried previous effective frame through the same
  :mod:`repro.core.gating` kernels the in-scan gate traces — bit-identical
  decisions, pinned by the per-tick-vs-segment differential test.

Attach a tap with ``StreamServer.add_stream(..., events=True)`` (or through
``FleetController.add_stream``); packets ride on
``StreamFrameResult.events``.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any

import numpy as np

from repro.core import gating, mapping
from repro.fpca import telemetry

__all__ = ["EventPacket", "EventStats", "EventTap", "segment_events"]


class EventStats(telemetry.StatsView):
    """Per-tap event accounting, registry-backed (labels carry
    ``arch="events"`` and the stream id).

    * ``ticks``      — gate ticks observed (packets emitted, incl. empty)
    * ``packets``    — packets emitted (== ticks; kept separate so a future
      coalescing tap stays honest)
    * ``events``     — total events (changed blocks) across all packets
    * ``events_pos`` / ``events_neg`` — polarity split; their sum is
      ``events`` *exactly* (asserted by ``assert_reconciled``)
    """

    _PREFIX = "fpca_events"
    _FIELDS = ("ticks", "packets", "events", "events_pos", "events_neg")


@dataclasses.dataclass(frozen=True)
class EventPacket:
    """One tick's address-events for one stream.

    ``coords`` are block-grid coordinates ``(row, col)`` on the
    ``grid_shape`` grid (``block`` effective pixels per side);  ``polarity``
    is the sign of the mean block intensity change (+1 brighter, -1
    darker).  ``timestamp`` is the host emission wall-clock (for segment
    reconstruction: when the packet was rebuilt, not when the tick ran).
    A tick whose delta crossed no threshold (or the stream's first frame,
    which has no delta) emits an *empty* packet — per-tick alignment with
    the serving loop is part of the contract.
    """

    stream_id: str
    frame_idx: int
    coords: np.ndarray               # (n, 2) int32 block (row, col)
    polarity: np.ndarray             # (n,) int8 in {+1, -1}
    timestamp: float
    grid_shape: tuple[int, int]
    block: int

    @property
    def n_events(self) -> int:
        return int(self.coords.shape[0])

    def raster(self) -> np.ndarray:
        """Signed event grid: +1 / -1 at event blocks, 0 elsewhere."""
        grid = np.zeros(self.grid_shape, np.int8)
        if self.n_events:
            grid[self.coords[:, 0], self.coords[:, 1]] = self.polarity
        return grid


def _packet(
    stream_id: str,
    frame_idx: int,
    changed: np.ndarray | None,
    signed: np.ndarray | None,
    grid_shape: tuple[int, int],
    block: int,
) -> EventPacket:
    if changed is None or not changed.any():
        coords = np.zeros((0, 2), np.int32)
        polarity = np.zeros((0,), np.int8)
    else:
        ys, xs = np.nonzero(changed)
        coords = np.stack([ys, xs], axis=-1).astype(np.int32)
        polarity = np.where(signed[ys, xs] >= 0, 1, -1).astype(np.int8)
    return EventPacket(
        stream_id=stream_id,
        frame_idx=int(frame_idx),
        coords=coords,
        polarity=polarity,
        timestamp=time.time(),
        grid_shape=grid_shape,
        block=block,
    )


class EventTap:
    """Per-stream event emitter over a :class:`StreamSession`'s delta gate.

    Requires a gated, shared-gate session (per-config fan-out gates would
    emit ambiguous per-block decisions).  ``packets`` retains the last
    ``history`` packets; :attr:`stats` is the registry-backed accounting.
    """

    def __init__(self, session: Any, history: int = 512):
        if session.per_config:
            raise NotImplementedError(
                "event streams need one shared gate per stream; per-config "
                "fan-out gates are unsupported"
            )
        if not session.gating:
            raise ValueError(
                f"event stream needs a gated stream; stream "
                f"{session.stream_id!r} is dense"
            )
        self.session = session
        session.want_events = True     # session computes the signed delta
        spec = session.spec
        self.grid_shape = gating.block_grid(spec)
        self.block = int(spec.skip_block)
        self.stats = EventStats(
            labels={"arch": "events", "stream": session.stream_id}
        )
        self.packets: collections.deque[EventPacket] = collections.deque(
            maxlen=history
        )

    def observe_tick(self, frame_idx: int) -> EventPacket:
        """Emit this tick's packet from the gate state the session just
        stepped.  Reads the *same* ``changed`` array the gate's
        ``changed_total`` counted — the per-tick reconciliation contract."""
        st = self.session._primary
        packet = _packet(
            self.session.stream_id,
            frame_idx,
            st.last_changed,
            self.session._last_signed,
            self.grid_shape,
            self.block,
        )
        self._record(packet)
        return packet

    def absorb_packets(self, packets: list[EventPacket]) -> None:
        """Fold segment-reconstructed packets (:func:`segment_events`) into
        the tap AND the gate-side changed-block accounting — the in-scan
        gate never touched the host ``_GateState``, so both sides of the
        reconciliation advance here in lock-step (the segment differential
        test pins the packet counts to the in-scan gate's decisions)."""
        st = self.session._primary
        for p in packets:
            self._record(p)
            st.changed_total += p.n_events

    def _record(self, packet: EventPacket) -> None:
        self.stats.ticks += 1
        self.stats.packets += 1
        n = packet.n_events
        if n:
            pos = int((packet.polarity > 0).sum())
            self.stats.events += n
            self.stats.events_pos += pos
            self.stats.events_neg += n - pos
        self.packets.append(packet)


def segment_events(
    spec: mapping.FPCASpec,
    frames: Any,
    prev_eff: Any | None,
    threshold: float,
    stream_id: str,
    first_frame_idx: int,
) -> list[EventPacket]:
    """Re-derive per-tick event packets for a device-compiled segment.

    ``frames`` are the segment's served ticks (``(ticks, H, W, c_i)``);
    ``prev_eff`` the effective frame carried *into* the segment (``None``
    at stream start); ``threshold`` the gate threshold the segment traced
    (captured *before* the boundary servo actuates).  Each tick is one
    :meth:`~repro.core.gating.HostGateKernels.step_signed` dispatch, the
    jitted :mod:`repro.core.gating` kernel whose vmapped twin the per-tick
    gate runs, so the changed-block decisions are bit-identical to what the
    device computed.
    """
    kernels = gating.host_gate_kernels(spec)
    grid_shape = gating.block_grid(spec)
    block = int(spec.skip_block)
    prev = None if prev_eff is None else np.asarray(prev_eff, np.float32)
    packets: list[EventPacket] = []
    for t, frame in enumerate(np.asarray(frames, np.float32)):
        if prev is None:
            cur = kernels.eff(frame)
            changed = signed = None
        else:
            cur, delta, signed_d = kernels.step_signed(prev, frame)
            changed = np.asarray(delta) > np.float32(threshold)
            signed = np.asarray(signed_d)
        packets.append(
            _packet(stream_id, first_frame_idx + t, changed, signed,
                    grid_shape, block)
        )
        prev = cur
    return packets
