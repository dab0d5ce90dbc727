"""Host time in ``BatchRouter.route_window`` per serving window. The
plans are host objects, so the call has synced with the device DP."""
UNIT = "ms/window"
LAYER = "routing (serving/batch_router.BatchRouter.route_window)"
MOVES = "itl_p90_ms"


def read(ctx):
    calls = ctx.calls["route"]
    return sum(b - a for a, b in calls) / len(calls) * 1e3 if calls else None
