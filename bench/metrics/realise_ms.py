"""Mean time to realise one tick (the wait on its counts, their and the
logits' copies to the host, unpacking): the ``realise`` span's mean
duration."""

from bench.spans import mean_ms


def read(ctx):
    return mean_ms(ctx.spans, "realise")
