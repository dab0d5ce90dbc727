"""Process start to the first wave: imports, weights, server build,
warm-up and any compilation."""
UNIT = "s"


def read(ctx):
    return ctx.setup_s
