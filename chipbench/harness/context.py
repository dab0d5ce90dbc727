"""What a metric reader is given: the window's host stamps, the reduced
trace of the traced wave, and the shapes the work counts need. A reader
(``chipbench/metrics/<name>.py``) defines ``UNIT``, ``LAYER``, ``MOVES``
and ``read(ctx)``, which returns a number or None when the run holds
nothing for it to read."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np


@dataclass
class ServedRequest:
    """One request of the window, as plain host data."""
    prompt_len: int
    max_new_tokens: int
    tokens: int
    emits: List[float]            # host stamps of its emitted tokens
    modelled_itl_ms: List[float]  # ServeMetrics.itl_ms(): sim clock


@dataclass
class WaveRecord:
    t_due: float
    t_end: float
    requests: List[ServedRequest]


@dataclass
class RunContext:
    model: dict                   # the configuration's "model" entry
    waves: List[WaveRecord]
    calls: Dict[str, List[Tuple[float, float]]]  # host spans in the window
    #: per routed window: (R, P, L, K, device DP called, host start)
    route_calls: List[Tuple[int, int, int, int, bool, float]]
    compiles_in_window: int
    setup_s: float
    peak: dict
    trace: Optional[object] = None          # trace.Reduced of wave 0
    traced_wave: Optional[WaveRecord] = None

    @property
    def window(self) -> Tuple[float, float]:
        return self.waves[0].t_due, self.waves[-1].t_end

    @property
    def window_s(self) -> float:
        t0, t1 = self.window
        return t1 - t0

    @property
    def requests(self) -> List[ServedRequest]:
        return [r for w in self.waves for r in w.requests]

    def tokens(self) -> int:
        return sum(r.tokens for r in self.requests)


def percentile(values, q: float) -> Optional[float]:
    """numpy's linear-interpolation percentile, None without samples."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))
