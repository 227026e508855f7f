"""Device time of the edge passes' scatters per traced traversal: the
operations of the bin and LB pass programs (``layers/bin_passes.json``,
``layers/lb_pass.json``) under the named scope ``combine`` (the
scatter-min or scatter-add into the labels), from the trace's op
metadata (``bench/xspace.py``)."""
from bench import xspace


def read(ctx):
    scopes = getattr(ctx.trace, "scopes", None)
    if not scopes:
        return None
    s = xspace.seconds_under(
        scopes, ctx.layers["bin_passes"] + ctx.layers["lb_pass"],
        ("combine",))
    return None if s is None else 1e3 * s / ctx.traversals
