"""Share of its roofline that the Pallas K-best routing kernel reaches in
the traced wave: the least time the chip needs for the DP's work,
counted from (R, P, L, K) by ``work.kbest_dp_work``, over the kernel's
device time in the trace."""
from chipbench.harness import work

UNIT = "%"
LAYER = "kernel (kernels/tropical_route.tropical_route_kbest)"
MOVES = "itl_p90_ms"
#: the kernel's op in a TPU trace: the Pallas custom call
#: ``%tropical_route_kbest.N = (...) custom-call(...)``
KERNEL = "%tropical_route_kbest"


def read(ctx):
    if ctx.trace is None:
        return None
    spent = sum(s for name, s in ctx.trace.ops_s.items()
                if name.startswith(KERNEL) and "custom-call" in name)
    w0, w1 = ctx.traced_wave.t_due, ctx.traced_wave.t_end
    need = sum(work.roofline_seconds(*work.kbest_dp_work(R, P, L, K),
                                     ctx.peak)
               for R, P, L, K, device, t0 in ctx.route_calls
               if device and w0 <= t0 <= w1)
    if spent <= 0 or need <= 0:
        return None
    return 100.0 * need / spent
