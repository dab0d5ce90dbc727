"""Host time in ``AdmissionQueue.next_window`` (admission and registry
sweep) per serving window."""
UNIT = "ms/window"
LAYER = "admission and registry sweep (serving/engine.AdmissionQueue.next_window)"
MOVES = "itl_p90_ms"


def read(ctx):
    calls = ctx.calls["admit"]
    return sum(b - a for a, b in calls) / len(calls) * 1e3 if calls else None
