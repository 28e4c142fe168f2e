"""Readings of the program's telemetry spans, as a traced run collects them
(the JSONL ``span`` records of ``repro.fpca.telemetry``): a phase's host
time a tick, a span's mean duration, and the wait between the end of a
tick's dispatch and the start of its realisation.  Each returns None where
the spans it reads are missing, as they are in a program without them."""

SERVE_TICK = "serve_tick"
REALISE = "realise"


def _named(spans, name):
    return [e for e in spans if e.get("span") == name]


def per_tick_ms(spans, name):
    """The durations of the spans named ``name``, summed over the traced
    part and divided by the count of ``serve_tick`` spans there, in ms."""
    ticks = len(_named(spans, SERVE_TICK))
    durs = [e["dur_s"] for e in _named(spans, name)]
    if not ticks or not durs:
        return None
    return 1e3 * sum(durs) / ticks


def mean_ms(spans, name):
    """Mean duration of the spans named ``name``, in ms."""
    durs = [e["dur_s"] for e in _named(spans, name)]
    return 1e3 * sum(durs) / len(durs) if durs else None


def inflight_ms(spans):
    """Mean, over the tick ids that have both, of a ``realise`` span's
    start less the end of the ``serve_tick`` span of the same tick, in ms:
    how long a dispatched tick waits before its results are read back."""
    ends = {e["tick"]: e["t0_ns"] + 1e9 * e["dur_s"]
            for e in _named(spans, SERVE_TICK) if "tick" in e and "t0_ns" in e}
    waits = [e["t0_ns"] - ends[e["tick"]] for e in _named(spans, REALISE)
             if e.get("tick") in ends and "t0_ns" in e]
    return 1e-6 * sum(waits) / len(waits) if waits else None
