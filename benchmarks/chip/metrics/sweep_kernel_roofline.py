"""Share of the partition-sweep kernel's roofline: the least time of the
DP's operations and bytes (counted from N, the read slots and the Q lanes)
at the chip's peaks, over the summed device time of the kernel's events.

The kernel is found by the name of its custom call in the trace's op line
(``%sweep_columns_call``, the jitted wrapper's name): the Pallas body's own
name does not reach the trace.
"""

KERNEL = "%sweep_columns_call"


def read(ctx):
    from chipbench.trace import matching_seconds

    t = matching_seconds(ctx.trace.ops, KERNEL)
    if t <= 0:
        return None
    ops = sum(u.get("ops", 0) for u in ctx.units)
    nbytes = sum(u.get("bytes", 0) for u in ctx.units)
    least = max(ops / ctx.peaks.flops_per_s, nbytes / ctx.peaks.hbm_bytes_per_s)
    return 100.0 * least / t
