"""Host work per design query that the device waits on, in ms: the
program's ``engine.solve`` spans less their ``sweep.readback`` children
(the host waiting for the kernel, then the copy back), over the queries
completed in the traced window."""


def read(ctx):
    from chipbench.spans import children, named

    solves = named(ctx.spans, "engine.solve")
    waits = children(ctx.spans, solves, "sweep.readback")
    n = sum(u.get("queries", 0) for u in ctx.units)
    if not waits or not n:
        return None
    host_us = sum(e["dur"] for e in solves) - sum(e["dur"] for e in waits)
    return 1e-3 * host_us / n
