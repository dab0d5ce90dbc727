"""Batched serving engine: prefill + decode with greedy/temperature
sampling, EOS detection, and a window admission queue (static batching;
the trust-routed pipeline server in gtrac_serve.py layers G-TRAC on top
and shares ``AdmissionQueue`` for its window-batched routing loop).

Submission goes through the unified ``SubmitSpec`` surface
(serving/api.py). Request ids come from the admission queue's monotonic
counter, never from queue-state arithmetic.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.api import build_model
from repro.serving.api import SubmitSpec


@dataclass
class Request:
    request_id: int
    prompt: np.ndarray                  # (S,) int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    output: List[int] = field(default_factory=list)
    done: bool = False
    # per-request trust floor for trust-routed serving (gtrac_serve.py);
    # None -> the router's configured floor. Plain engines ignore it.
    tau: Optional[float] = None
    # sim-clock arrival (seconds): admission defers until the window
    # clock reaches it (0.0 = already arrived, the classic behavior)
    arrival_time: float = 0.0
    # stream kind for disaggregated serving: auto | prefill | decode
    kind: str = "auto"

    @classmethod
    def from_spec(cls, spec: SubmitSpec, request_id: int) -> "Request":
        return cls(request_id=int(request_id),
                   prompt=np.asarray(spec.prompt, np.int32),
                   max_new_tokens=int(spec.max_new_tokens),
                   eos_id=spec.eos_id, tau=spec.tau,
                   arrival_time=float(spec.arrival_time), kind=spec.kind)


class AdmissionQueue:
    """FIFO admission with window batching and arrival-time gating.

    Pending requests are admitted in windows of at most ``max_batch``:
    the plain engine drains whole windows into its static batcher, the
    trust-routed pipeline server tops its active stream set up to the
    window size each token step (continuous batching). Factored out of
    ``ServingEngine`` so both serving layers share one admission policy.

    The queue owns the request-id space: ``next_request_id()`` is a
    monotonic counter (seeded by ``id_base``), so ids stay unique under
    any interleaving of submissions and window pops — the old
    ``len(queue) + admitted`` arithmetic collided as soon as requests
    entered the queue by any path other than the engine's own submit
    (hand-built ``Request`` objects, capacity-deferred arrivals).

    ``registry`` (any ``repro.core.sharding.Registry`` — monolithic or
    sharded anchor) couples admission to registry hygiene: each window pop
    that carries a clock runs one ``sweep(now)`` before requests are
    admitted, so TTL expiry / trust decay land ahead of the window's
    routing DP. With a sharded registry the sweep fans out per shard and
    clean shards no-op without touching their snapshot versions.
    """

    def __init__(self, max_batch: int = 64, registry=None, id_base: int = 0):
        self.max_batch = int(max_batch)
        self.registry = registry     # Optional[repro.core.sharding.Registry]
        self.pending: List[Request] = []
        self.admitted = 0
        self.swept_peers = 0         # total peers TTL-expired by our sweeps
        self._next_id = int(id_base)

    def __len__(self) -> int:
        return len(self.pending)

    def next_request_id(self) -> int:
        """Allocate the next request id (monotonic, never reused)."""
        rid = self._next_id
        self._next_id += 1
        return rid

    def submit(self, req: Request) -> Request:
        # explicit ids above the counter advance it past them, so a later
        # auto-allocated id can never collide with a pinned one
        self._next_id = max(self._next_id, req.request_id + 1)
        self.pending.append(req)
        return req

    def next_arrival(self) -> Optional[float]:
        """Earliest pending arrival time (None when the queue is empty) —
        the window scheduler's idle-jump target."""
        if not self.pending:
            return None
        return min(r.arrival_time for r in self.pending)

    def next_window(self, capacity: Optional[int] = None,
                    now: Optional[float] = None) -> List[Request]:
        """Pop the next admission window (up to min(max_batch, capacity))
        of *arrived* requests (``arrival_time <= now``; a missing clock
        admits everything). When a registry and a clock are supplied,
        sweep first."""
        if self.registry is not None and now is not None:
            self.swept_peers += self.registry.sweep(now)
        n = self.max_batch if capacity is None \
            else max(0, min(self.max_batch, capacity))
        if now is None:
            window, self.pending = self.pending[:n], self.pending[n:]
        else:
            window, rest = [], []
            for r in self.pending:
                if len(window) < n and r.arrival_time <= now:
                    window.append(r)
                else:
                    rest.append(r)
            self.pending = rest
        self.admitted += len(window)
        return window

    @staticmethod
    def by_prompt_length(reqs: List[Request]) -> Dict[int, List[Request]]:
        """Group a window by prompt length (padding a causal prompt shifts
        RoPE positions and leaks attention onto pad tokens; bucketing is
        the standard fix)."""
        groups: Dict[int, List[Request]] = {}
        for r in reqs:
            groups.setdefault(len(r.prompt), []).append(r)
        return groups

    @staticmethod
    def split_by_kind(reqs: List[Request], prefill_threshold: int)\
            -> Tuple[List[Request], List[Request]]:
        """Classify a window into (prefill, decode) streams.

        The prompt-length buckets decide the split: buckets longer than
        ``prefill_threshold`` (one prefill chunk) become dedicated
        prefill streams; the rest prefill inline in their first decode
        step. A request's explicit ``kind`` ("prefill"/"decode")
        overrides its bucket."""
        prefill: List[Request] = []
        decode: List[Request] = []
        for length, group in sorted(
                AdmissionQueue.by_prompt_length(reqs).items()):
            for r in group:
                if r.kind == "prefill" or \
                        (r.kind == "auto" and length > prefill_threshold):
                    prefill.append(r)
                else:
                    decode.append(r)
        return prefill, decode


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, capacity_margin: int = 64,
                 max_batch: int = 64):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.params = params
        self.margin = capacity_margin
        self._prefill = jax.jit(
            lambda p, toks, cap: self.model.prefill(p, tokens=toks,
                                                    capacity=cap),
            static_argnames=("cap",))
        self._decode = jax.jit(self.model.decode_step)
        self.admission = AdmissionQueue(max_batch=max_batch)

    @property
    def queue(self) -> List[Request]:
        return self.admission.pending

    def submit(self, spec: SubmitSpec) -> Request:
        """Queue one stream."""
        rid = (self.admission.next_request_id()
               if spec.request_id is None else spec.request_id)
        return self.admission.submit(Request.from_spec(spec, rid))

    def run_batch(self, reqs: Optional[List[Request]] = None,
                  greedy: bool = True, temperature: float = 1.0,
                  seed: int = 0) -> List[Request]:
        """Serve requests to completion, admitted in queue windows and
        grouped by prompt length (``AdmissionQueue.by_prompt_length``)."""
        if reqs is None:
            served: List[Request] = []
            while len(self.admission):
                served += self.run_batch(self.admission.next_window(),
                                         greedy, temperature, seed)
            return served
        if not reqs:
            return []
        for group in AdmissionQueue.by_prompt_length(reqs).values():
            self._run_equal_batch(group, greedy, temperature, seed)
        return reqs

    def _run_equal_batch(self, reqs: List[Request], greedy: bool,
                         temperature: float, seed: int) -> List[Request]:
        toks = np.stack([r.prompt for r in reqs])
        max_new = max(r.max_new_tokens for r in reqs)
        cap = toks.shape[1] + max_new + self.margin
        logits, cache = self._prefill(self.params, jnp.asarray(toks), cap)
        key = jax.random.PRNGKey(seed)
        cur = None
        for t in range(max_new):
            if cur is None:
                step_logits = logits
            else:
                step_logits, cache = self._decode(self.params, cur, cache)
            if greedy:
                nxt = jnp.argmax(step_logits[:, -1, :], axis=-1)
            else:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(
                    sub, step_logits[:, -1, :] / temperature, axis=-1)
            cur = nxt[:, None].astype(jnp.int32)
            nxt_np = np.asarray(nxt)
            for i, r in enumerate(reqs):
                if r.done or t >= r.max_new_tokens:
                    continue
                tok = int(nxt_np[i])
                r.output.append(tok)
                if r.eos_id is not None and tok == r.eos_id:
                    r.done = True
            if all(r.done or len(r.output) >= r.max_new_tokens
                   for r in reqs):
                break
        for r in reqs:
            r.done = True
        return reqs
