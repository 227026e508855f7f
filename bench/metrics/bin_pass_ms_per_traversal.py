"""Device time of the programs in ``layers/bin_passes.json`` per traced
traversal."""


def read(ctx):
    s = ctx.layer_seconds("bin_passes")
    return None if s is None else 1e3 * s / ctx.traversals
