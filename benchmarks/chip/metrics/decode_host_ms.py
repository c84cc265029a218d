"""Mean self time of the program's ``serve.decode`` span, in ms: the
dispatch and host work around each decode program, less its
``serve.token_sync`` child (the token's readback)."""


def read(ctx):
    from chipbench.spans import named, self_times

    decodes = [e for e in named(ctx.spans, "serve.decode") if "id" in e]
    if not decodes:
        return None
    own = self_times(ctx.spans)
    return 1e-3 * sum(own[e["id"]] for e in decodes) / len(decodes)
