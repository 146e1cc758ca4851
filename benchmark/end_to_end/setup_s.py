"""Process start to the first timed statement: imports, the server, the warm-up."""


def read(ctx):
    return ctx.setup_s
