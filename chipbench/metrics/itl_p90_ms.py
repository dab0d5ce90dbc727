"""Host-clock gap between consecutive tokens of a stream, 90th
percentile over every gap in the window.

The gaps are not independent: the streams of a wave share one host pass
per serving window, so one slow window stretches up to 32 gaps at once.
A window of 4 waves holds ~120 serving windows; the 90th percentile has
~12 of them beyond it, where the 99th rests on the slowest one or two."""
from chipbench.harness.context import percentile

UNIT = "ms"


def read(ctx):
    return percentile([(b - a) * 1e3 for r in ctx.requests
                       for a, b in zip(r.emits, r.emits[1:])], 90)
