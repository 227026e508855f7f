"""The edge passes' share of their roofline: the least bytes the traced
traversals have to move (``bench/work.py``) at the chip's HBM peak, over
the device time of the bin and LB pass programs.  Bytes bound it: the
passes do one compare-and-add per edge."""


def read(ctx):
    s = ctx.layer_seconds("bin_passes", "lb_pass")
    if not s:
        return None
    return 100.0 * ctx.least_bytes / ctx.peaks["hbm_bytes_per_s"] / s
