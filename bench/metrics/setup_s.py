"""Set-up: process start to window start (imports, weights, engine,
warm-up and, on a first run, compiles).  Host clock."""


def read(ctx):
    return ctx.setup_s
