"""Device time of the programs in ``layers/inspector.json`` per traced
traversal."""


def read(ctx):
    s = ctx.layer_seconds("inspector")
    return None if s is None else 1e3 * s / ctx.traversals
