"""Generated tokens of the requests completed in the window, per second."""


def read(ctx):
    n = sum(u.get("tokens", 0) for u in ctx.units)
    return n / ctx.window_s if n else None
