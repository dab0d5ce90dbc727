"""Host-clock time from a request's due time (its wave's ``run_queue``
call) to its first emitted token, 90th percentile over the window's
requests. A request that emitted nothing is left out here and counted in
``failed``."""
from chipbench.harness.context import percentile

UNIT = "ms"


def read(ctx):
    return percentile([(r.emits[0] - w.t_due) * 1e3 for w in ctx.waves
                       for r in w.requests if r.emits], 90)
