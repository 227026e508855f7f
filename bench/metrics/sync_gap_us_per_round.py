"""Device idle time under the host round's ``graph.counts`` span (the
count program's dispatch and the round's one device-to-host transfer)
per round of the traced traversals: the part of
``host_gap_us_per_round`` the host spends waiting on the counts."""


def read(ctx):
    idle = getattr(ctx.trace, "idle_by_span", None)
    if not idle or "graph.counts" not in idle or ctx.rounds == 0:
        return None
    return 1e6 * idle["graph.counts"] / ctx.rounds
