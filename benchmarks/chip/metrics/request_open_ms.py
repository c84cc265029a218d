"""Mean duration of the program's ``serve.open`` span, in ms: opening a
request (plan and parameters, its task graph, cycle prices, the burst
runtime and the prompt's seeding)."""


def read(ctx):
    from chipbench.spans import named

    opens = named(ctx.spans, "serve.open")
    return 1e-3 * sum(e["dur"] for e in opens) / len(opens) if opens else None
