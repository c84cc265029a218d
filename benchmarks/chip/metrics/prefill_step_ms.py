"""Device time of the jitted prefill program over the prefills run, in ms.

The program is found by its jit name (``_prefill``) among the trace's
module executions.
"""

PROGRAM = "_prefill"


def read(ctx):
    from chipbench.trace import matching_seconds

    n = sum(u.get("prefills", 0) for u in ctx.units)
    t = matching_seconds(ctx.trace.modules, PROGRAM)
    if not n or t <= 0:
        return None
    return 1e3 * t / n
