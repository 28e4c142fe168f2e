"""Reduction of a profiler trace to device busy time, kernel time and the
``breakdown`` of a traced run.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes; it is read with
``jax.profiler.ProfileData``.  Device planes are named ``/device:TPU:<i>``;
their ``XLA Ops`` line holds one event per operation run on the chip.  Host
annotations (the benchmark's ``bench:*`` spans and the program's
``fpca:<site>:<backend>`` launch annotations) sit on host-plane lines, on the
same clock.  The traced window is the host annotation ``bench:traced``.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW = "bench:traced"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


@dataclass
class Device:
    ops: list = field(default_factory=list)   # (start_ns, end_ns, name)
    busy: list = field(default_factory=list)  # merged (start_ns, end_ns)

    @property
    def busy_ns(self) -> float:
        return float(sum(e - s for s, e in self.busy))


@dataclass
class Trace:
    window: tuple            # (start_ns, end_ns)
    devices: dict            # index -> Device
    host: list               # (start_ns, end_ns, name) host annotations

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(d.busy_ns for d in self.devices.values()) / len(self.devices) * 1e-9

    def op_seconds(self, pattern: re.Pattern) -> float:
        """Summed device time of the operations whose name matches."""
        return sum((e - s) for d in self.devices.values() for s, e, n in d.ops
                   if pattern.search(n)) * 1e-9


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str, devices: int | None = None) -> Trace:
    """Read a trace; only operations inside the window are kept (clipped)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, window, raw = [], None, {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            idx = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    raw[idx] = [(e.start_ns, e.end_ns, e.name) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.end_ns)
                    elif e.name.startswith(("bench:", "fpca:")):
                        host.append((e.start_ns, e.end_ns, e.name))
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in {path}")
    lo, hi = window
    devs = {}
    for idx, ops in sorted(raw.items()):
        if devices is not None and idx >= devices:
            continue
        kept = [(max(s, lo), min(e, hi), n) for s, e, n in ops if e > lo and s < hi]
        devs[idx] = Device(ops=kept, busy=_merge((s, e) for s, e, _ in kept))
    host = [(s, e, n) for s, e, n in host if e > lo and s < hi]
    return Trace(window=window, devices=devs, host=host)


def to_json(trace: Trace) -> dict:
    """The reduced trace (window, device operations, host annotations) as
    plain JSON, for a recorded trace that the self-test pins."""
    return {"window": list(trace.window), "host": [list(h) for h in trace.host],
            "devices": {str(i): [list(op) for op in d.ops]
                        for i, d in trace.devices.items()}}


def from_json(data: dict) -> Trace:
    devs = {}
    for i, ops in data["devices"].items():
        ops = [tuple(op) for op in ops]
        devs[int(i)] = Device(ops=ops, busy=_merge((s, e) for s, e, _ in ops))
    return Trace(window=tuple(data["window"]), devices=devs,
                 host=[tuple(h) for h in data["host"]])


_HLO = re.compile(r"^(%\S+) = .*?\s([a-z][\w-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(name: str) -> str:
    """An operation's name in the trace is its HLO text; keep the result's
    name, the operation and a custom call's target: ``%run.1 custom-call
    tpu_custom_call``."""
    m = _HLO.match(name)
    if not m:
        return name
    t = _TARGET.search(name)
    return " ".join([m.group(1), m.group(2)] + ([t.group(1)] if t else []))


def top_ops(trace: Trace, n: int = 10) -> list:
    """Device operations that took the most time, summed by name and
    averaged over the devices: ``[[name, seconds], ...]``."""
    tot: dict = {}
    for d in trace.devices.values():
        for s, e, name in d.ops:
            name = short_name(name)
            tot[name] = tot.get(name, 0.0) + (e - s) * 1e-9
    k = max(len(trace.devices), 1)
    return [[name, t / k] for name, t in sorted(tot.items(), key=lambda x: -x[1])[:n]]


def _host_label(trace: Trace, t: float) -> str:
    """The innermost host annotation open at time ``t``."""
    best = None
    for s, e, name in trace.host:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "unannotated"


def idle_gaps(trace: Trace, n: int = 10) -> list:
    """The longest idle gaps of the first device inside the window, each
    labelled by what the host was doing at its midpoint."""
    if not trace.devices:
        return []
    dev = trace.devices[min(trace.devices)]
    lo, hi = trace.window
    edges = [lo] + [x for iv in dev.busy for x in iv] + [hi]
    gaps = [(edges[i + 1] - edges[i], edges[i]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(reverse=True)
    return [[_host_label(trace, s + d / 2), d * 1e-9] for d, s in gaps[:n]]
