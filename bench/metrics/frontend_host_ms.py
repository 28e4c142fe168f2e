"""Host time a tick spends in the frontend handle call (kept-window counts
per shard, bucket choice, padding, the keep grid's copy, the launch): the
``frontend`` span's durations per ``serve_tick``."""

from bench.spans import per_tick_ms


def read(ctx):
    return per_tick_ms(ctx.spans, "frontend")
