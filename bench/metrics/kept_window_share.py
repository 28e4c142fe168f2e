"""Share of windows the delta gate kept over the window, from the
``StreamStats`` counters ``windows_kept / windows_total``."""


def read(ctx):
    total = ctx.stats.get("windows_total", 0)
    return 100.0 * ctx.stats["windows_kept"] / total if total else None
