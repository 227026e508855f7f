"""Programs built inside the traced window (an XLA compilation or a
load from the persistent cache), counted by ``jax.monitoring``: a shape
the warm-up missed."""


def read(ctx):
    return ctx.compiles_in_window
