"""Percent of the traced window (the window's first unit) in which no
operation ran on the device: 100 * (1 - busy / window)."""


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
