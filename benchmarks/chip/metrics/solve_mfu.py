"""The design query's least time at the chip's peaks (the DP's counted
operations and bytes) over its wall time per query, in %. It stays valid
whatever kernel does the work."""


def read(ctx):
    n = sum(u.get("queries", 0) for u in ctx.units)
    if not n:
        return None
    ops = sum(u.get("ops", 0) for u in ctx.units)
    nbytes = sum(u.get("bytes", 0) for u in ctx.units)
    least = max(ops / ctx.peaks.flops_per_s, nbytes / ctx.peaks.hbm_bytes_per_s)
    return 100.0 * least / ctx.window_s
