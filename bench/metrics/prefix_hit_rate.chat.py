"""Share of the page pool's prefix lookups that hit a shared page, over
the window (the pool's own ``prefix_hits`` / ``prefix_lookups``)."""


def read(ctx):
    c = ctx["rec"].get("counters") or {}
    if not c.get("prefix_lookups"):
        return None
    return 100.0 * c["prefix_hits"] / c["prefix_lookups"]
