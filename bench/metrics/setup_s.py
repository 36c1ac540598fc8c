"""Process start to the window's first request: imports, data, build,
warm-up."""


def read(ctx):
    return ctx.setup_s
