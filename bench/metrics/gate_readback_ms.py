"""Host time a tick spends in the gate's batched dispatch and the read-back
of its block |Δ| grids (which waits for the staged frames' upload and the
vmapped gate): the ``gate_readback`` span's durations per ``serve_tick``.
A program without the span reads None."""

from bench.spans import per_tick_ms


def read(ctx):
    return per_tick_ms(ctx.spans, "gate_readback")
