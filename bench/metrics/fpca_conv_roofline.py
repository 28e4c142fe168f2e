"""Roofline share of the Pallas frontend kernel: the least time the chip
could take for the kept windows' work (``bench/work.py``, unpadded shapes),
the larger of FLOPs over the bf16 peak and bytes over HBM bandwidth, over the
summed device time of the kernel's operations in the trace."""

import re

# The Pallas call has no name of its own: on a TPU v5e trace it is the HLO
# custom call ``%run.N = f32[M,128] custom-call(...)`` with
# ``custom_call_target="tpu_custom_call"``, the program's only Mosaic kernel.
KERNEL = re.compile(r"tpu_custom_call")


def read(ctx):
    if ctx.peak is None:          # no chip, no peak
        return None
    if ctx.trace is None:
        return None
    t = ctx.trace.op_seconds(KERNEL)
    windows = ctx.stats.get("windows_kept", 0)
    if t <= 0 or not windows:
        return None
    flops, nbytes = ctx.work.kernel_work(ctx.cfg, windows)
    f_s, b_s = flops / ctx.peak["bf16_flops"], nbytes / ctx.peak["hbm_bytes_per_s"]
    ctx.note(f"fpca_conv kernel: {t:.6f} s device time over {windows} kept windows; "
             f"bound by {'HBM bytes' if b_s >= f_s else 'FLOPs'} "
             f"({b_s:.6f} s bytes, {f_s:.6f} s FLOPs)")
    return 100.0 * max(f_s, b_s) / t
