"""Share of the traced wave in which no op ran on the device."""
UNIT = "%"
LAYER = "device (TPU v5e)"
MOVES = "tokens_per_s"


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
