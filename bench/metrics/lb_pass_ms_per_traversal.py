"""Device time of the programs in ``layers/lb_pass.json`` per traced
traversal."""


def read(ctx):
    s = ctx.layer_seconds("lb_pass")
    return None if s is None else 1e3 * s / ctx.traversals
