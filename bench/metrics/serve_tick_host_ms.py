"""Mean host time of one ``StreamServer.run`` tick dispatch (gating,
stacking and enqueue), from the ``serve_tick`` telemetry span's ``dur_s``."""


def read(ctx):
    durs = [e["dur_s"] for e in ctx.spans if e.get("span") == "serve_tick"]
    return 1e3 * sum(durs) / len(durs) if durs else None
