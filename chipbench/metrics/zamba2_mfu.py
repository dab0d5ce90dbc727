"""Useful model FLOPs of the tokens emitted in the traced wave over the
chip's bfloat16 peak for the wave's length, counted for Zamba2
(``work_zamba2.token_flops``): each prompt once, causally, and one
position per later token; the served path's recomputed prefixes do not
count."""
from chipbench.harness import work_zamba2

UNIT = "%"
LAYER = "whole step (serving/gtrac_serve.run_queue)"
MOVES = "tokens_per_s"


def read(ctx):
    if ctx.trace is None:
        return None
    flops = sum(work_zamba2.token_flops(ctx.model, r.prompt_len, i)
                for r in ctx.traced_wave.requests for i in range(r.tokens))
    if not flops:
        return None
    return 100.0 * flops / (ctx.trace.window_s * ctx.peak["flops_bf16"])
