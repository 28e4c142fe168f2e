"""Host time a tick spends in the delta gate (the batched gate dispatch, its
read-back and the per-stream gate steps): the ``gate`` span's durations per
``serve_tick``."""

from bench.spans import per_tick_ms


def read(ctx):
    return per_tick_ms(ctx.spans, "gate")
