"""Megabytes (10**6 B) copied from the host to the device per camera-frame
dispatched: ``StreamStats.h2d_bytes`` over ``frames`` in the traced part."""


def read(ctx):
    nbytes, frames = ctx.stats.get("h2d_bytes"), ctx.stats.get("frames")
    if nbytes is None or not frames:
        return None
    return nbytes / frames / 1e6
