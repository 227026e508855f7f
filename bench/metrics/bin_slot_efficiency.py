"""Share of the slots the bin passes issued that carried a frontier
edge, over the traced traversals: 100 x ``bin_edges`` / ``bin_slots``
(the program's counters, ``repro.core.balancer.counter_snapshot``).
A bin pass issues capacity x width slots per chunk, the capacity the
bin's size rounded up to a power of two."""


def read(ctx):
    c = getattr(ctx, "counters", None)
    if not c or not c.get("bin_slots"):
        return None
    return 100.0 * c["bin_edges"] / c["bin_slots"]
