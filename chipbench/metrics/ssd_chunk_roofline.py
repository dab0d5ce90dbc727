"""Share of its roofline that the Pallas SSD scan reaches in the traced
wave: the least time the chip needs for the scan work the tokens
required, over the device time of the kernel's calls in the trace. The
work, from shapes (``work_zamba2``): per Mamba2 layer, the chunked scan
over each prompt once, then one recurrent step for each later token.
Recomputed prefixes do not count, so the share reads the same work
whatever implements it."""
from chipbench.harness import work, work_zamba2

UNIT = "%"
LAYER = "state-space scan (kernels/ssd_chunk.ssd_chunked)"
MOVES = "tokens_per_s"
#: the kernel's op in a TPU trace: the Pallas custom call
#: ``%ssd_chunk.N = (...) custom-call(...)``
KERNEL = "%ssd_chunk"


def read(ctx):
    if ctx.trace is None:
        return None
    spent = sum(s for name, s in ctx.trace.ops_s.items()
                if name.startswith(KERNEL) and "custom-call" in name)
    m, peak = ctx.model, ctx.peak
    step = work.roofline_seconds(*work_zamba2.ssd_step_work(m), peak)
    need = int(m["n_layer"]) * sum(
        work.roofline_seconds(*work_zamba2.ssd_chunked_work(m, r.prompt_len),
                              peak) + step * max(0, r.tokens - 1)
        for r in ctx.traced_wave.requests if r.tokens)
    if spent <= 0 or need <= 0:
        return None
    return 100.0 * need / spent
