"""Set-up: process start to the first timed operation, compiles included."""


def read(ctx):
    return ctx.setup_s
