"""Megabytes (10**6 B) read back from the device to the host per
camera-frame dispatched: ``StreamStats.d2h_bytes`` over ``frames`` in the
traced part.  The gate's read-back is billed at dispatch and the counts'
and logits' at realisation, so the ticks already in flight when the trace
starts add their realised bytes to the traced part."""


def read(ctx):
    nbytes, frames = ctx.stats.get("d2h_bytes"), ctx.stats.get("frames")
    if nbytes is None or not frames:
        return None
    return nbytes / frames / 1e6
