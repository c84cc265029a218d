"""Window divided by the design queries completed in it, in ms."""


def read(ctx):
    n = sum(u.get("queries", 0) for u in ctx.units)
    return 1e3 * ctx.window_s / n if n else None
