"""Requests admitted onto a cached prefix, of those admitted in the window."""


def read(ctx):
    admitted = ctx["counters"].get("engine.admitted")
    hits = ctx["counters"].get("engine.prefix_hits")
    if not admitted or not hits:
        return None
    return 100.0 * hits / admitted
