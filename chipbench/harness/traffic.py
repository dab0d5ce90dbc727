"""The one traffic generator: closed-loop waves drawn from a mix file.

A mix (``chipbench/traffic/<name>.json``) gives the requests per wave and
a clipped log-normal for prompt and output lengths, beside notes on its
``source``, its ``cut`` and what is ``assumed``, which the generator does
not read:

    {"wave_requests": 32,
     "prompt_tokens": {"min": 197, "max": 245, "median": 221, "sigma": 0.067},
     "output_tokens": {"min": 16, "max": 48, "median": 28, "sigma": 0.35}}

A wave submits all its requests at once; the next starts when the server
has drained it. Lengths are drawn stratified: wave ``w`` of seed ``s``
takes one uniform draw inside each of ``n`` equal quantile strata, so
every wave of every seed has nearly the same multiset of lengths, in
another order, and seeds change which tokens are served, not how much
work a wave is. Token ids are uniform over the vocabulary. Every
request is served at the deployment's configured trust floor.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np

#: stream tags, so warm-up and window waves never share draws
WINDOW, WARMUP = 0, 1

_STD_NORMAL = NormalDist()


@dataclass(frozen=True)
class RequestSpec:
    prompt: np.ndarray          # (S,) int32 token ids
    max_new_tokens: int


def _lengths(spec: dict, u: np.ndarray) -> np.ndarray:
    """Clipped log-normal lengths at quantiles ``u`` in (0, 1)."""
    z = np.array([_STD_NORMAL.inv_cdf(float(q)) for q in u])
    raw = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(raw, spec["min"], spec["max"]).astype(int)


def _stratified(rng: np.random.Generator, n: int) -> np.ndarray:
    u = (rng.permutation(n) + rng.random(n)) / n
    return np.clip(u, 1e-9, 1 - 1e-9)


def wave(mix: dict, seed: int, index: int, vocab: int,
         stream: int = WINDOW,
         max_new_tokens: Optional[int] = None) -> List[RequestSpec]:
    """The requests of wave ``index`` of ``seed``: the same seed, stream
    and index always give the same requests."""
    rng = np.random.default_rng([int(seed) % 2 ** 64, stream, index])
    n = int(mix["wave_requests"])
    prompts = _lengths(mix["prompt_tokens"], _stratified(rng, n))
    outputs = _lengths(mix["output_tokens"], _stratified(rng, n))
    if max_new_tokens is not None:
        outputs = np.minimum(outputs, max_new_tokens)
    return [RequestSpec(rng.integers(0, vocab, int(p), dtype=np.int32), int(o))
            for p, o in zip(prompts, outputs)]


def prefix_lengths(mix: dict) -> range:
    """Every token-sequence length a stage program sees under ``mix``:
    from the shortest prompt to the longest prompt plus all but the last
    of the longest output."""
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    return range(int(p["min"]), int(p["max"]) + int(o["max"]))


def length_walk(mix: dict, seed: int, vocab: int) -> List[RequestSpec]:
    """Warm-up requests that reach every shape the mix can produce: one
    stream from the shortest prompt through the longest prefix, and one
    single-token stream per other prompt length."""
    rng = np.random.default_rng([int(seed) % 2 ** 64, WARMUP, 2 ** 31])
    lengths = prefix_lengths(mix)
    p = mix["prompt_tokens"]
    lo, hi = int(p["min"]), int(p["max"])
    walk = [RequestSpec(rng.integers(0, vocab, lo, dtype=np.int32),
                        len(lengths))]
    return walk + [RequestSpec(rng.integers(0, vocab, n, dtype=np.int32), 1)
                   for n in range(lo + 1, hi + 1)]

