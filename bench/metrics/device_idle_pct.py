"""Share of the traced window, in %, in which no operation ran on the
device: 1 - (union of busy intervals / window)."""


def read(ctx):
    v = ctx.view
    if v.window_s <= 0 or not v.busy:
        return None
    return 100.0 * (1.0 - v.busy_s / v.window_s)
