"""Host time a tick spends dispatching the skip-aware digital head: the
``head`` span's durations per ``serve_tick``."""

from bench.spans import per_tick_ms


def read(ctx):
    return per_tick_ms(ctx.spans, "head")
