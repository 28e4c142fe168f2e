"""Host time a tick spends in the gate's host pass over its streams (the
threshold compare, ages, keyframes, keep and window grids, servos and
result entries): the ``gate_streams`` span's durations per ``serve_tick``.
A program without the span reads None."""

from bench.spans import per_tick_ms


def read(ctx):
    return per_tick_ms(ctx.spans, "gate_streams")
