"""Share of the traced window in which no instruction ran, mean over the
cell's chips (device trace)."""


def read(ctx):
    tr = ctx.trace
    if not tr.ops or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.mean_busy_s() / tr.window_s)
