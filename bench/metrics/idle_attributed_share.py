"""Share of the first device's idle time in the traced window during which
the host was inside a program phase: the part of the idle intervals covered
by the union of the ``fpca:`` host annotations other than the root
``fpca:serve_tick`` (phase spans, realisation, launches)."""

ROOT_SPAN = "fpca:serve_tick"


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a, b):
    """Summed length of the intersection of two sorted disjoint lists."""
    i = j = 0
    total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(ctx):
    t = ctx.trace
    if t is None or not t.devices:
        return None
    lo, hi = t.window
    busy = t.devices[min(t.devices)].busy
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    idle = [[edges[i], edges[i + 1]] for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]]
    idle_ns = sum(e - s for s, e in idle)
    if idle_ns <= 0:
        return None
    phases = _union((max(s, lo), min(e, hi)) for s, e, name in t.host
                    if name.startswith("fpca:") and name != ROOT_SPAN
                    and e > lo and s < hi)
    return 100.0 * _overlap(idle, phases) / idle_ns
