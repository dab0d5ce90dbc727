"""Plain K-shortest-path reference for the router, in float64 numpy.

The routing problem of one window (G-TRAC, Eq. 4 and Alg. 1 line 1): a
peer that hosts layers [start, end) is an edge from boundary ``start`` to
boundary ``end``; it is usable when it is alive and its trust is at least
the request's floor; its cost is ``latency + (1 - trust) * T_timeout``.
A chain is a path from boundary 0 to boundary L. ``kbest`` returns the
costs of the K cheapest distinct chains, ascending.
"""
from __future__ import annotations

from typing import List

import numpy as np


def edge_costs(latency_ms, trust, timeout_ms: float) -> np.ndarray:
    return (np.asarray(latency_ms, np.float64)
            + (1.0 - np.asarray(trust, np.float64)) * float(timeout_ms))


def usable(alive, trust, floor: float, starts, ends, L: int) -> np.ndarray:
    """Alive, valid, and trusted to the floor. Trust is compared in
    float32, the precision the router keeps it in on the device, so a
    trust within float32 rounding of the floor reads as the router
    reads it."""
    starts, ends = np.asarray(starts), np.asarray(ends)
    trusted = np.asarray(trust, np.float32) >= np.float32(floor)
    return (np.asarray(alive, bool) & trusted
            & (starts >= 0) & (starts < ends) & (ends <= L))


def kbest(starts, ends, cost, ok, L: int, K: int) -> List[float]:
    """Costs of the K cheapest chains from boundary 0 to ``L``."""
    best: List[List[float]] = [[] for _ in range(L + 1)]
    best[0] = [0.0]
    for b in range(1, L + 1):
        cand = [d + float(cost[p]) for p in np.nonzero(ok & (ends == b))[0]
                for d in best[int(starts[p])]]
        best[b] = sorted(cand)[:K]
    return best[L]
