"""Host time a tick spends staging its batch (stacking the frames and keep
grids, the frames' copy to the device): the ``stage`` span's durations per
``serve_tick``."""

from bench.spans import per_tick_ms


def read(ctx):
    return per_tick_ms(ctx.spans, "stage")
