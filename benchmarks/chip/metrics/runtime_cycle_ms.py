"""Mean duration of the burst runtime's ``burst`` span (one energy cycle:
restore, token steps, NVM commit), in ms."""


def read(ctx):
    durs = [e["dur"] for e in ctx.spans or [] if e.get("name") == "burst"]
    return 1e-3 * sum(durs) / len(durs) if durs else None
