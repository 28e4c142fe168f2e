"""The whole step's share of the chips' bf16 peak: the dense model's FLOPs
per frame (``bench/work.py``) times the frames per second served in the
traced window, over the chips' peak."""


def read(ctx):
    if ctx.peak is None:          # no chip, no peak
        return None
    if not ctx.frames_per_s:
        return None
    peak = ctx.chips * ctx.peak["bf16_flops"]
    return 100.0 * ctx.model_flops_per_frame * ctx.frames_per_s / peak
