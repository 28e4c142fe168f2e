"""Mean wait between the end of a tick's dispatch (``serve_tick``) and the
start of its realisation (``realise``, same ``tick``): what the server's
depth-2 buffering adds to a live camera's latency."""

from bench.spans import inflight_ms


def read(ctx):
    return inflight_ms(ctx.spans)
