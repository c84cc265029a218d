"""Model FLOPs of every token processed (prompt and generated) per second
of the traced window, over the chips' bf16 peak, in %."""


def read(ctx):
    flops = sum(u.get("flops", 0) for u in ctx.units)
    if not flops:
        return None
    return 100.0 * flops / ctx.window_s / (ctx.peaks.flops_per_s * ctx.chips)
