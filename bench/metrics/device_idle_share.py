"""Share of the traced window in which no operation ran on the chip: one
minus the union of the device's operation intervals over the window,
averaged over the chips."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0 or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
