"""Share of the edge ids the LB pass enumerated that were frontier
edges, over the traced traversals: 100 x ``lb_edges`` / ``lb_slots``
(the program's counters, ``repro.core.balancer.counter_snapshot``).
The pass enumerates the huge bin's edge total rounded up to a power of
two, then to a multiple of the tiles."""


def read(ctx):
    c = getattr(ctx, "counters", None)
    if not c or not c.get("lb_slots"):
        return None
    return 100.0 * c["lb_edges"] / c["lb_slots"]
