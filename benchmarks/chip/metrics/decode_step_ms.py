"""Device time of the jitted decode program over the decode steps run, in ms.

The program is found by its jit name (``_decode``) among the trace's
module executions.
"""

PROGRAM = "_decode"


def read(ctx):
    from chipbench.trace import matching_seconds

    steps = sum(u.get("decode_steps", 0) for u in ctx.units)
    t = matching_seconds(ctx.trace.modules, PROGRAM)
    if not steps or t <= 0:
        return None
    return 1e3 * t / steps
