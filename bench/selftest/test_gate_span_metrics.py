"""The readers of the gate's two child spans, ``gate_readback`` and
``gate_streams``, on hand-made spans; and their silence where a program
lacks the spans."""

from types import SimpleNamespace

import pytest

from bench import harness

METRICS = harness.BENCH / "metrics"


def _reader(base):
    return harness.load_module(METRICS / f"{base}.py")


def _span(name, t0_ns, dur_s, tick=None):
    e = {"event": "span", "span": name, "t0_ns": t0_ns, "dur_s": dur_s}
    if tick is not None:
        e["tick"] = tick
    return e


# two ticks, each gate split into its read-back and its host pass
SPANS = [
    _span("serve_tick", 0, 0.010, tick=0),
    _span("gate_readback", 0, 0.001), _span("gate_streams", 1_000_000, 0.003),
    _span("gate", 0, 0.004),
    _span("serve_tick", 100_000_000, 0.010, tick=1),
    _span("gate_readback", 100_000_000, 0.004),
    _span("gate_streams", 104_000_000, 0.002),
    _span("gate", 100_000_000, 0.006),
]


def _ctx(spans):
    return SimpleNamespace(spans=list(spans), stats={}, trace=None)


@pytest.mark.parametrize("base, want", [
    ("gate_readback_ms", 2.5), ("gate_streams_ms", 2.5), ("gate_host_ms", 5.0),
])
def test_gate_span_readers(base, want):
    assert _reader(base).read(_ctx(SPANS)) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("base", ["gate_readback_ms", "gate_streams_ms"])
def test_gate_span_readers_are_silent_without_their_spans(base):
    """A program whose ``gate`` span has no children: nothing to read."""
    old = [s for s in SPANS if not s["span"].startswith("gate_")]
    assert _reader(base).read(_ctx(old)) is None
