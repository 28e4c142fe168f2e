"""Device time of the digital head per tick: the summed device time of the
head's operations in the traced window (``bench/head_ops.py``) over the
count of ``serve_tick`` spans there."""

from bench.head_ops import head_seconds


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    t = head_seconds(ctx.trace)
    ticks = sum(1 for e in ctx.spans if e.get("span") == "serve_tick")
    if t <= 0 or not ticks:
        return None
    return 1e3 * t / ticks
