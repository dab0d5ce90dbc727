"""The comparisons that decide ``correct``.

Stage compute: for a sample of the requests the window finished, drawn
from the seed with the longest among them, the plain reference runs once
over each prompt with its served tokens. Each served token's reading is
the gap by which the reference's logit for it lies below the reference's
best logit at that position; the number compared is the widest gap.

Routing: every plan served in the window is checked against a plain
K-shortest-path DP on the table it was routed on: the plan holds as many
chains as the reference finds (up to K); each chain is a valid chain of
usable peers for its floor whose stated cost is its true cost; its
costs are the reference's K best; and its primary is the cheapest. The number compared is the count of
windows with a plan that fails, and its limit is 0.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

#: relative tolerance of a routed cost against the float64 reference:
#: the device DP adds at most L float32 costs, each rounded by 2**-24
COST_RTOL = 1e-5


@dataclass
class Served:
    """What the window served for one request, as plain host data."""
    prompt: np.ndarray
    output: List[int]
    max_new_tokens: int


def _chain_ok(rows: List[int], cost: float, starts, ends, ok, c, L) -> bool:
    if not rows or int(starts[rows[0]]) != 0 or int(ends[rows[-1]]) != L:
        return False
    for a, b in zip(rows, rows[1:]):
        if int(ends[a]) != int(starts[b]):
            return False
    if not all(ok[r] for r in rows):
        return False
    true = float(sum(c[r] for r in rows))
    return abs(true - cost) <= COST_RTOL * max(1.0, abs(true))


def plan_ok(rec, floor: float, chains, costs, L: int, K: int,
            timeout_ms: float, ref) -> bool:
    c = ref.edge_costs(rec.latency_ms, rec.trust, timeout_ms)
    ok = ref.usable(rec.alive, rec.trust, floor, rec.starts, rec.ends, L)
    want = ref.kbest(rec.starts, rec.ends, c, ok, L, K)
    if len(chains) != len(want) or len(costs) != len(chains):
        return False
    if len({tuple(ch) for ch in chains}) != len(chains):
        return False
    if not all(_chain_ok(ch, cost, rec.starts, rec.ends, ok, c, L)
               for ch, cost in zip(chains, costs)):
        return False
    if costs and costs[0] > want[0] + COST_RTOL * max(1.0, abs(want[0])):
        return False                   # the primary is not the cheapest
    return all(abs(a - b) <= COST_RTOL * max(1.0, abs(b))
               for a, b in zip(sorted(costs), want))


def route_mismatches(records: Sequence, gtrac: dict, L: int, ref)\
        -> Tuple[int, Optional[str]]:
    """Windows whose served plans disagree with the reference, and a
    description of the first."""
    bad, first = 0, None
    K, timeout = int(gtrac["k_best_routes"]), float(gtrac["request_timeout_ms"])
    for i, rec in enumerate(records):
        for floor, chains, costs in rec.plans:
            if not plan_ok(rec, floor, chains, costs, L, K, timeout, ref):
                bad += 1
                first = first or (f"window {i}: floor {floor}, plan costs "
                                  f"{costs}, chains {chains}")
                break
    return bad, first


def sample(served: Sequence[Served], seed: int, n: int) -> List[Served]:
    """``n`` finished requests drawn from the seed, the longest among
    them."""
    done = [s for s in served if len(s.output) == s.max_new_tokens]
    if len(done) <= n:
        return list(done)
    longest = max(range(len(done)),
                  key=lambda i: len(done[i].prompt) + len(done[i].output))
    rng = np.random.default_rng([int(seed) % 2 ** 64, 7])
    rest = [i for i in rng.permutation(len(done)) if i != longest][:n - 1]
    return [done[longest]] + [done[i] for i in sorted(rest)]


def batch(samples: Sequence[Served], length: int):
    """(B, length) tokens, zero-padded after each sequence, and the mask
    of the positions whose next token was served."""
    toks = np.zeros((len(samples), length), np.int32)
    mask = np.zeros((len(samples), length - 1), bool)
    for i, s in enumerate(samples):
        seq = np.concatenate([s.prompt, np.asarray(s.output, np.int32)])
        if len(seq) > length:
            raise ValueError(f"sequence of {len(seq)} > {length}")
        toks[i, :len(seq)] = seq
        p = len(s.prompt)
        mask[i, p - 1:p - 1 + len(s.output)] = True
    return toks, mask


def served_gaps(ref_logits, tokens, mask) -> np.ndarray:
    """Per served token: the reference's best logit minus its logit for
    the token served."""
    import jax.numpy as jnp

    lg = ref_logits[:, :-1]
    got = jnp.take_along_axis(lg, jnp.asarray(tokens)[:, 1:, None], -1)[..., 0]
    gap = jnp.max(lg, -1) - got
    return np.asarray(gap)[np.asarray(mask)]

