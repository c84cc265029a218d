"""Idle share of the device over the traced window of the serving loop."""


def read(ctx):
    if ctx.busy_s is None or ctx.busy_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
