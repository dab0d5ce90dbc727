"""Output tokens emitted in the window over the window's host seconds."""
UNIT = "tokens/s"


def read(ctx):
    return ctx.tokens() / ctx.window_s
