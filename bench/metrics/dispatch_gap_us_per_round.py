"""Device idle time under the host round's ``graph.plan``,
``graph.passes`` and ``graph.update`` spans (the host planning and
dispatching the round's programs) per round of the traced traversals:
the part of ``host_gap_us_per_round`` spent between programs."""

SPANS = ("graph.plan", "graph.passes", "graph.update")


def read(ctx):
    idle = getattr(ctx.trace, "idle_by_span", None)
    hit = [idle[s] for s in SPANS if idle and s in idle]
    if not hit or ctx.rounds == 0:
        return None
    return 1e6 * sum(hit) / ctx.rounds
