"""Device idle time in the traced window per round of the traced
traversals: what the host round loop costs beyond the device's work."""


def read(ctx):
    if ctx.rounds == 0:
        return None
    t = ctx.trace
    return 1e6 * (t.window_s - t.busy_s) / ctx.rounds
