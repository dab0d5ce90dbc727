"""Host time inside ``ChainExecutor.execute`` per emitted token: hop
dispatch and the sim's bookkeeping (the device runs asynchronously)."""
UNIT = "ms/token"
LAYER = "chain execution (core/executor.ChainExecutor.execute)"
MOVES = "tokens_per_s"


def read(ctx):
    tokens = ctx.tokens()
    if not tokens:
        return None
    return sum(b - a for a, b in ctx.calls["chain"]) / tokens * 1e3
