"""Device time of the edge passes' gathers per traced traversal: the
operations of the bin and LB pass programs (``layers/bin_passes.json``,
``layers/lb_pass.json``) under the named scopes ``edges`` (slot to edge
id, the ``col_idx``/``edge_w`` gathers) and ``sources`` (the
``fmask``/``values`` gathers, the message), from the trace's op
metadata (``bench/xspace.py``)."""
from bench import xspace


def read(ctx):
    scopes = getattr(ctx.trace, "scopes", None)
    if not scopes:
        return None
    s = xspace.seconds_under(
        scopes, ctx.layers["bin_passes"] + ctx.layers["lb_pass"],
        ("edges", "sources"))
    return None if s is None else 1e3 * s / ctx.traversals
