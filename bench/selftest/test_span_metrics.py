"""The readers of the per-tick phase spans, the byte counters and the idle
share the phases cover, on hand-made spans, counters and traces; and their
silence where a program lacks the spans or counters."""

from types import SimpleNamespace

import pytest

from bench import harness, trace

METRICS = harness.BENCH / "metrics"


def _reader(base):
    return harness.load_module(METRICS / f"{base}.py")


def _span(name, t0_ns, dur_s, tick=None):
    e = {"event": "span", "span": name, "t0_ns": t0_ns, "dur_s": dur_s}
    if tick is not None:
        e["tick"] = tick
    return e


# two ticks: serve_tick k at 100k ms for 10 ms, its phases inside, and its
# realisation 50 ms after its dispatch ended (tick -1 was in flight before)
SPANS = [
    _span("realise", 5_000_000, 0.002, tick=-1),
    _span("serve_tick", 0, 0.010, tick=0),
    _span("gate", 0, 0.004), _span("stage", 4_000_000, 0.003),
    _span("frontend", 7_000_000, 0.002), _span("head", 9_000_000, 0.001),
    _span("serve_tick", 100_000_000, 0.010, tick=1),
    _span("gate", 100_000_000, 0.006), _span("stage", 106_000_000, 0.001),
    _span("frontend", 107_000_000, 0.002), _span("head", 109_000_000, 0.0005),
    _span("realise", 60_000_000, 0.004, tick=0),
    _span("realise", 170_000_000, 0.006, tick=1),
]


def _ctx(spans=(), stats=None, tr=None):
    return SimpleNamespace(spans=list(spans), stats=stats or {}, trace=tr)


@pytest.mark.parametrize("base, want", [
    ("gate_host_ms", 5.0), ("stage_host_ms", 2.0), ("frontend_host_ms", 2.0),
    ("head_host_ms", 0.75), ("realise_ms", 4.0), ("inflight_ms", 55.0),
])
def test_span_readers(base, want):
    assert _reader(base).read(_ctx(SPANS)) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("base", [
    "gate_host_ms", "stage_host_ms", "frontend_host_ms", "head_host_ms",
    "realise_ms", "inflight_ms", "h2d_mb_per_frame", "d2h_mb_per_frame",
])
def test_readers_are_silent_without_their_spans_or_counters(base):
    """A program with only ``serve_tick`` spans (no tick ids, no start
    times) and no byte counters: nothing to read, and no error."""
    old = [{"event": "span", "span": "serve_tick", "dur_s": 0.01}]
    assert _reader(base).read(_ctx(old, {"frames": 256.0})) is None


@pytest.mark.parametrize("base, field", [("h2d_mb_per_frame", "h2d_bytes"),
                                         ("d2h_mb_per_frame", "d2h_bytes")])
def test_byte_readers(base, field):
    stats = {"frames": 256.0, field: 256 * 8_805_888.0}
    assert _reader(base).read(_ctx(stats=stats)) == pytest.approx(8.805888)
    assert _reader(base).read(_ctx(stats={field: 1.0, "frames": 0.0})) is None


def _trace(host):
    dev = trace.Device(ops=[(10, 20, "a"), (60, 70, "b")])
    dev.busy = trace._merge((s, e) for s, e, _ in dev.ops)
    return trace.Trace(window=(0, 100), devices={0: dev}, host=host)


def test_idle_attributed_share():
    """Idle: [0,10) [20,60) [70,100) = 80 ns.  Phases cover [0,10) and
    [25,45) (the root serve_tick and the bench's spans do not count) and
    [90,120) clipped to [90,100): 40 ns of it."""
    host = [(0, 100, "bench:next"), (0, 95, "fpca:serve_tick"),
            (0, 10, "fpca:gate"), (25, 40, "fpca:stage"),
            (30, 45, "fpca:frontend:pallas"), (90, 120, "fpca:realise"),
            (60, 70, "fpca:head")]
    got = _reader("idle_attributed_share").read(_ctx(tr=_trace(host)))
    assert got == pytest.approx(50.0)


def test_idle_attributed_share_of_a_program_without_phase_spans():
    host = [(0, 100, "bench:next"), (5, 8, "fpca:frontend:pallas")]
    read = _reader("idle_attributed_share").read
    assert read(_ctx(tr=_trace(host))) == pytest.approx(3 / 80 * 100)
    assert read(_ctx(tr=trace.Trace(window=(0, 100), devices={}, host=host))) is None
    assert read(_ctx()) is None
