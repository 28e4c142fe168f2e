"""Roofline share of the digital head: the least time the chip could take
for the head's work on the frames dispatched in the traced part (the
``head_work`` of the configuration's head module,
``bench/configs/<name>.py``, from unpadded shapes), the larger of FLOPs over
the bf16 peak and bytes over HBM bandwidth, over the summed device time of
the head's operations in the trace (``bench/head_ops.py``).  Silent for a
configuration whose head module has no ``head_work``."""

from bench import harness
from bench.head_ops import head_seconds


def read(ctx):
    if ctx.peak is None or ctx.trace is None:     # no chip, no peak
        return None
    head = harness.load_module(harness.BENCH / "configs" / f"{ctx.cfg['name']}.py")
    work = getattr(head, "head_work", None)
    frames = ctx.stats.get("frames", 0)
    t = head_seconds(ctx.trace)
    if work is None or t <= 0 or not frames:
        return None
    flops, nbytes = work(ctx.cfg, int(frames))
    f_s, b_s = flops / ctx.peak["bf16_flops"], nbytes / ctx.peak["hbm_bytes_per_s"]
    ctx.note(f"head: {t:.6f} s device time over {int(frames)} frames; bound by "
             f"{'HBM bytes' if b_s >= f_s else 'FLOPs'} "
             f"({b_s:.6f} s bytes, {f_s:.6f} s FLOPs)")
    return 100.0 * max(f_s, b_s) / t
