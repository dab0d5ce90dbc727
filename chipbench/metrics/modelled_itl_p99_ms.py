"""Sim-clock gap between consecutive tokens (``ServeMetrics.itl_ms()``),
99th percentile over every gap of the window's requests: the edge latency
a user of the modelled fleet would feel, which moves with route quality."""
from chipbench.harness.context import percentile

UNIT = "ms"


def read(ctx):
    return percentile([g for r in ctx.requests for g in r.modelled_itl_ms],
                      99)
