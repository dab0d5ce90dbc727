"""Device time of the ``stage_step`` programs in the traced wave per
token emitted in it."""
UNIT = "ms/token"
LAYER = "stage compute (serving/gtrac_serve.stage_step)"
MOVES = "tokens_per_s"
PROGRAM = "stage_step"


def read(ctx):
    if ctx.trace is None:
        return None
    spent = sum(s for name, s in ctx.trace.modules_s.items()
                if PROGRAM in name)
    tokens = sum(r.tokens for r in ctx.traced_wave.requests)
    if spent <= 0 or not tokens:
        return None
    return spent / tokens * 1e3
