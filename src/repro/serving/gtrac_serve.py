"""Trust-aware routed pipeline serving — the paper's system, end to end,
with REAL model compute.

The served model is split into contiguous layer stages (StagePartition).
Each *peer* is a stage replica with its own latency/reliability profile
(sim/peers.py); the Anchor tracks trust; the Seeker routes each token's
chain from its cached view (G-TRAC / any baseline), and the ChainExecutor
runs the hops — each hop executes the stage's actual jitted forward on the
hidden states, exactly the paper's layer-sharded activation relay. Hop
payloads are stateless (full-prefix recompute per token), matching the
paper's testbed semantics and making Bounded One-Shot Repair trivially
correct: a replacement peer needs no KV-state transfer.

Peers do, however, retain per-stream KV for their own stage, so the
window-batched loop (``run_queue``) prices every hop by the tokens it must
*freshly* process: a hop routed back to a warm peer pays for the increment,
a cold hop recomputes the prefix (serving/kv_cache.KVLocalityTracker).
``cfg.kv_reuse_bonus`` folds that locality into routing as a per-request
edge-cost discount — the batched K-best DP prefers, never requires, the
warm chain. ``cfg.disaggregate`` splits admission windows by prompt length
(AdmissionQueue.split_by_kind): long prompts prefill in dedicated chunked
windows (``cfg.prefill_chunk_tokens`` per chunk, at most the decode token
budget per window) that run asynchronously against the decode cadence and
hand their warm streams to the continuous decode pool.

This powers examples/serve_gtrac.py and the integration tests.
"""
from __future__ import annotations

import functools
import gc
import time
import weakref
from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import GTRACConfig, ModelConfig
from repro.core.executor import ChainExecutor, split_reports
from repro.core.hedging import HedgedChainExecutor
from repro.core.planner import RoutePlanner, plan_route
from repro.core.registry import SeekerCache
from repro.core.routing import ALGORITHMS
from repro.core.sharding import make_registry
from repro.core.types import HopReport
from repro.distributed.pipeline import StagePartition
from repro.models.api import build_model
from repro.obs.metrics import MetricsRegistry, percentiles
from repro.obs.trace import (
    HOST_DOMAIN,
    NOOP_SPAN,
    NOOP_TRACER,
    TraceBuffer,
    Tracer,
)
from repro.serving.api import SubmitSpec
from repro.serving.batch_router import BatchRouter
from repro.serving.engine import AdmissionQueue, Request, _deprecated_submit
from repro.serving.kv_cache import KVLocalityTracker
from repro.sim.peers import PROFILES, SimPeer, make_peer
from repro.sim.testbed import Testbed
from repro.sync.gossip import make_sync_plane


# ---------------------------------------------------------------------------
# Real stage compute
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg", "first", "last"))
def stage_step(cfg: ModelConfig, stage_params, tokens, x, length=None, *,
               first: bool, last: bool):
    """One stage's forward, by the model family's ``stage_forward``. The
    weights are arguments, never constants of the program, so stages of
    one structure share one executable per prefix length (per bucket of
    lengths where the family pads, ``Model.stage_bucket``);
    ``served_stage_params`` gives them in the dtypes the forward
    consumes. Stage 0 embeds ``tokens``; the last stage returns the last
    position's logits instead of hidden states, at ``length - 1`` for
    right-padded ``tokens``."""
    kw = {} if length is None else {"length": length}
    return build_model(cfg).stage_forward(stage_params, tokens, x,
                                          first=first, last=last, **kw)


@jax.jit
def greedy_token(logits):
    """The greedy next token of each row of the last stage's logits
    ``(B, 1, V)``, as ``(B,)`` int32 on the device: one program for
    every prefix length, and nothing read by the host."""
    return jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)


def served_stage_params(cfg: ModelConfig, params,
                        partition: StagePartition) -> List[dict]:
    """Each stage's weights as ``stage_step`` takes them, by the model
    family's ``served_stages``: its slice of the layers and what else
    its layers read (the embedding and final norm; a hybrid model's
    shared blocks), with the matmul weights and embedding tables in the
    activation dtype. The forward consumes those only at that dtype;
    held at float32, each call of a stage program would convert its
    whole weight stack again. ``params`` stay the masters and are not
    changed."""
    return build_model(cfg).served_stages(
        params, [partition.segment(i) for i in range(partition.n_stages)])


def weight_bytes(stages: Sequence[dict]) -> Dict[str, int]:
    """Bytes of the stages' weights per dtype (``bf16``, ``f32``),
    each array counted once however many stages share it."""
    held = {id(a): a for sp in stages for a in jax.tree.leaves(sp)}
    out: Dict[str, int] = {}
    for a in held.values():
        name = a.dtype.name.replace("bfloat", "bf").replace("float", "f")
        out[name] = out.get(name, 0) + a.nbytes
    return out


def shared_block_bytes(stages: Sequence[dict]) -> int:
    """Bytes of the shared blocks that the stages hold, counted once per
    stage that holds them: what sharing a block's weights across the
    depth costs once the depth is split into stages on separate peers."""
    total = 0
    for sp in stages:
        held = {id(a): a for h in sp.get("hybrid", {}).values()
                for a in jax.tree.leaves(h["block"])}
        total += sum(a.nbytes for a in held.values())
    return total


def make_stage_fns(cfg: ModelConfig, params, partition: StagePartition,
                   obs: Optional[MetricsRegistry] = None):
    """One hop fn per stage over a ``(tokens, x)`` payload, on the
    weights of ``served_stage_params``, made once, here. Where the family
    has a ``stage_bucket``, each hop right-pads ``tokens`` to a multiple
    of it, so ``x`` between stages holds the padded positions; the
    payload's ``tokens`` stay as they came. ``run_queue`` gives them as
    host ids, so the pad reads nothing from the device. ``obs`` gets
    the weights' bytes per dtype as gauges ``stage/weight_bytes_<dtype>``,
    and the shared blocks' as ``stage/shared_block_bytes``."""
    stages = served_stage_params(cfg, params, partition)
    if obs is not None:
        for name, n in weight_bytes(stages).items():
            obs.gauge(f"stage/weight_bytes_{name}").set(n)
        obs.gauge("stage/shared_block_bytes").set(shared_block_bytes(stages))
    n = len(stages)
    bucket = build_model(cfg).stage_bucket

    def stage_fn(i: int):
        sp = stages[i]
        flags = {"first": i == 0, "last": i == n - 1}

        def fn(payload):
            tokens, x = payload                     # x is None at stage 0
            if bucket is None:
                return tokens, stage_step(cfg, sp, tokens, x, **flags)
            B, S = tokens.shape
            padded = np.zeros((B, -(-S // bucket) * bucket), np.int32)
            padded[:, :S] = tokens
            return tokens, stage_step(cfg, sp, padded, x, np.int32(S),
                                      **flags)

        return fn

    return [stage_fn(i) for i in range(n)]


#: JAX's monitoring event for one backend compile (a persistent-cache
#: load fires it too)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def hook_process_spans(tracer) -> weakref.finalize:
    """Record ``gc`` and ``compile`` spans on ``tracer``, from the
    collector's callbacks and JAX's compile-duration events. Both carry
    their own durations, so the spans are synthesized with ``add`` under
    whatever span is open. Returns the callable that removes the hooks;
    they hold ``tracer`` weakly and are also removed when it is freed,
    so a server dropped without clearing its host tracer leaves no hook
    behind."""
    ref = weakref.ref(tracer)
    gc_t0 = None

    def on_gc(phase, info):
        nonlocal gc_t0
        tr = ref()
        if tr is not None and tr.enabled:
            if phase == "start":
                gc_t0 = tr.clock()
            elif gc_t0 is not None:
                tr.add("gc", gc_t0, tr.clock(), cat="process",
                       generation=info["generation"],
                       collected=info["collected"])
                gc_t0 = None

    def on_duration(name, secs, **_kw):
        tr = ref()
        if name == COMPILE_EVENT and tr is not None and tr.enabled:
            t1 = tr.clock()
            tr.add("compile", t1 - secs, t1, cat="process")

    def unhook():
        gc.callbacks.remove(on_gc)
        jax.monitoring.unregister_event_duration_listener(on_duration)

    gc.callbacks.append(on_gc)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return weakref.finalize(tracer, unhook)


def sample_token(logits, rng: np.random.Generator,
                 temperature: float = 1.0) -> int:
    """Temperature sampling off the testbed RNG: softmax of the last
    position's logits at ``temperature``, one categorical draw. Runs on
    host numpy — the testbed's RNG is the single source of randomness
    for the whole sim (failures, latencies, sampling), which keeps runs
    reproducible per seed."""
    z = np.asarray(logits, np.float64).reshape(-1)
    z = z / max(float(temperature), 1e-6)
    z -= z.max()
    p = np.exp(z)
    p /= p.sum()
    return int(rng.choice(len(p), p=p))


# ---------------------------------------------------------------------------
# Routed pipeline server
# ---------------------------------------------------------------------------


@dataclass
class ServeMetrics:
    tokens: int = 0
    failures: int = 0
    repairs: int = 0
    rerouted: int = 0
    token_latency_ms: List[float] = field(default_factory=list)
    infeasible: int = 0
    # hedged window serving (cfg.hedge_enabled): cumulative hedge counters
    # mirrored from the stream's HedgedChainExecutor after every window
    hedges_fired: int = 0
    hedges_won: int = 0
    # gossip serving (cfg.gossip_enabled): worst per-shard seeker-cache
    # staleness (in gossip rounds) seen while this stream was active
    stale_rounds_max: int = 0
    # relay serving (cfg.relay_enabled): cumulative relay-plane totals
    # (payloads delivered — data messages AND handshake summaries — and
    # measured seeker→seeker wire bytes) at stream completion
    relay_msgs: int = 0
    relay_bytes: int = 0
    # Byzantine hardening (cfg.relay_verify): duplicate deliveries the
    # handshake suppresses, plus the digest-verification outcome totals
    relay_duplicates: int = 0
    relay_digest_mismatches: int = 0
    relay_rejected_chains: int = 0
    relay_quarantines: int = 0
    # process control plane (cfg.control_plane="procs"): cumulative
    # composer health totals (control_plane/registry.py) at stream
    # completion — RPC deadline expiries / re-posts, windows served with
    # >= 1 degraded or dead shard, and worker respawns
    shard_rpc_retries: int = 0
    shard_timeouts: int = 0
    degraded_windows: int = 0
    worker_restarts: int = 0
    # streaming latency: sim-clock emission stamp (ms) of every token and
    # time-to-first-token relative to the request's arrival_time; ITL is
    # the diff of consecutive emission stamps (see ``itl_ms``)
    ttft_ms: float = -1.0                  # -1 until the first token lands
    emit_ms: List[float] = field(default_factory=list)
    # disaggregated serving (cfg.disaggregate): dedicated prefill windows
    # executed for this stream before it joined the decode pool
    prefill_chunks: int = 0
    prefill_tokens: int = 0
    # KV locality (serving/kv_cache.py): decode steps whose routed chain
    # held the stream's warm KV end to end vs. steps routed off it and
    # recomputing (first-contact steps with nothing to reuse count as
    # neither)
    kv_warm_hits: int = 0
    kv_cold_steps: int = 0

    def itl_ms(self) -> List[float]:
        """Inter-token latencies: diffs of consecutive emission stamps."""
        e = self.emit_ms
        return [b - a for a, b in zip(e, e[1:])]


def latency_summary(reqs: Sequence["RoutedRequest"]) -> Dict[str, float]:
    """Aggregate p50/p99 TTFT + inter-token latency, the warm-chain hit
    rate, and the completion rate over a set of served streams
    (launch/serve.py, benchmarks). Percentiles are -1.0 when no samples
    exist (``obs.metrics.percentiles`` — the repo-wide helper).

    A stream whose ``ttft_ms`` is still the -1 sentinel never emitted a
    token (infeasible route, unrepaired failure): it is counted as
    ``incomplete`` and excluded from the TTFT percentiles rather than
    silently poisoning them."""
    ttfts = [r.metrics.ttft_ms for r in reqs if r.metrics.ttft_ms >= 0]
    itls: List[float] = []
    for r in reqs:
        itls += r.metrics.itl_ms()
    warm = sum(r.metrics.kv_warm_hits for r in reqs)
    cold = sum(r.metrics.kv_cold_steps for r in reqs)
    t50, t99 = percentiles(ttfts, (50, 99))
    i50, i99 = percentiles(itls, (50, 99))
    n = len(reqs)
    completed = len(ttfts)
    return {"ttft_p50_ms": t50, "ttft_p99_ms": t99,
            "itl_p50_ms": i50, "itl_p99_ms": i99,
            "warm_hit_rate": warm / max(1, warm + cold),
            "requests": n, "completed": completed,
            "incomplete": n - completed,
            "completion_rate": completed / n if n else -1.0}


# ServeMetrics stream field <- obs.MetricsRegistry snapshot keys (summed).
# A field fills only when every key is present, i.e. when the layer that
# owns it was wired into the registry — absent layers leave the dataclass
# defaults, exactly like the old per-layer mirroring did.
_STREAM_VIEW: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("relay_msgs", ("relay/msgs", "relay/summaries")),
    ("relay_bytes", ("relay/wire_bytes",)),
    ("relay_duplicates", ("relay/duplicates",)),
    ("relay_digest_mismatches", ("relay/digest_mismatches",)),
    ("relay_rejected_chains", ("relay/rejected_chains",)),
    ("relay_quarantines", ("relay/quarantines",)),
    ("shard_rpc_retries", ("control_plane/rpc_retries",)),
    ("shard_timeouts", ("control_plane/rpc_timeouts",)),
    ("degraded_windows", ("control_plane/degraded_windows",)),
    ("worker_restarts", ("control_plane/worker_restarts",)),
)


@dataclass
class RoutedRequest(Request):
    """Engine admission request + per-stream routed serving state."""

    metrics: ServeMetrics = field(default_factory=ServeMetrics)
    # ChainExecutor, or HedgedChainExecutor when cfg.hedge_enabled
    executor: Optional[object] = None
    # disaggregated prefill progress: prompt tokens prefilled so far, the
    # sim time the in-flight chunk completes, and the first decode token
    # computed by the final chunk (emitted at promotion time)
    prefill_pos: int = 0
    busy_until: float = 0.0
    _pending_tok: int = 0

    def ids(self) -> np.ndarray:
        """The stream's token ids on the host, ``(1, S)`` int32: the
        prompt, then every token emitted so far."""
        return np.concatenate(
            [self.prompt, np.asarray(self.output, np.int32)])[None, :]


class GTRACPipelineServer:
    """Serve a model across simulated stage-replica peers under a routing
    policy. Peers execute REAL stage compute; failures/latency are injected
    per their profile; trust state evolves exactly as in the paper."""

    def __init__(self, cfg: ModelConfig, params,
                 layers_per_stage: int,
                 replicas: Dict[str, int] = None,
                 gcfg: Optional[GTRACConfig] = None,
                 algorithm: str = "gtrac",
                 seed: int = 0):
        self.cfg = cfg
        self.gcfg = gcfg or GTRACConfig()
        self.algorithm = algorithm
        self.partition = StagePartition.uniform(cfg.num_layers,
                                                layers_per_stage)
        # the telemetry plane (below) starts with the stage weights'
        # bytes per dtype
        self.obs = MetricsRegistry()
        # run_queue's device-to-host reads of token ids (one per window
        # that picked any) and the ids they carried
        self._token_reads = self.obs.counter("serve/token_reads")
        self._tokens_read = self.obs.counter("serve/tokens_read")
        self.stage_fns = make_stage_fns(cfg, params, self.partition,
                                        self.obs)
        rng = np.random.default_rng(seed)
        # any Registry (core/sharding.py): monolithic anchor for
        # cfg.anchor_shards=1, hash-partitioned ShardedAnchorRegistry
        # otherwise — the planner / window router consume its composed
        # snapshot unchanged
        anchor = make_registry(self.gcfg, shards=self.gcfg.anchor_shards,
                               shard_by=self.gcfg.shard_by)
        # process-backed control plane (cfg.control_plane="procs"): the
        # composer carries health counters and its own staleness-priced
        # routing_view (degraded shards' slices serve stale, discounted)
        self._cp = anchor if hasattr(anchor, "health") else None
        peers: Dict[int, SimPeer] = {}
        replicas = replicas or {"honeypot": 2, "turtle": 2, "golden": 2}
        pid = 0
        for i in range(self.partition.n_stages):
            s, e = self.partition.segment(i)
            for name, k in replicas.items():
                for _ in range(k):
                    peer = make_peer(pid, s, e, PROFILES[name], rng)
                    peers[pid] = peer
                    anchor.register(pid, s, e, now=0.0, profile=name)
                    anchor.heartbeat(pid, 0.0)
                    pid += 1
        self.bed = Testbed(cfg=self.gcfg, total_layers=cfg.num_layers,
                           peers=peers, anchor=anchor, rng=rng)
        self.seeker = SeekerCache(anchor, self.gcfg, now=0.0)
        # gossip sync plane (cfg.gossip_enabled): routing reads a
        # delta-synced shard-mirror cache (repro.sync) instead of the
        # in-process snapshot; staleness-bounded routing_view discounts
        # trust on shards the seeker cannot confirm
        self.gossip = None
        self.sync_seeker = None
        if self.gcfg.gossip_enabled:
            # routing reads seeker 0; with cfg.relay_enabled the rest of
            # cfg.gossip_seekers carry the epidemic relay plane (the
            # anchor then pushes only to gossip_fanout seeds per round)
            _, sync_seekers, self.gossip = make_sync_plane(
                anchor, self.gcfg,
                n_seekers=max(1, self.gcfg.gossip_seekers), now=0.0)
            self.sync_seeker = sync_seekers[0]
        # per-server planner: compiled CSR graph + K-best plans are reused
        # across every token routed from an unchanged registry snapshot
        self.planner = RoutePlanner(cfg.num_layers,
                                    k_best=self.gcfg.k_best_routes,
                                    cache_size=self.gcfg.planner_cache_size)
        # window-batched routing: concurrent streams submitted per token
        # window are solved in ONE batched device DP (serving/batch_router)
        self.router = BatchRouter(planner=self.planner, cfg=self.gcfg,
                                  total_layers=cfg.num_layers)
        # admission owns the per-window registry sweep (per-shard fan-out
        # when the anchor is sharded) AND the request-id space: ids come
        # from its monotonic counter, seeded clear of generate()'s
        self.admission = AdmissionQueue(max_batch=self.gcfg.router_max_batch,
                                        registry=anchor, id_base=10_000)
        # which peers hold which stream's warm KV — prices hops by freshly
        # processed tokens and feeds the router's chain-reuse bonus
        self.kv = KVLocalityTracker()
        # (request_id, peer_id) -> rescale factor for the last multi-token
        # hop charge; consumed by _apply_report before the anchor EMA
        self._tok_scale: Dict[Tuple[int, int], float] = {}
        self._stage_of = {}  # layer_start -> stage idx
        for i in range(self.partition.n_stages):
            self._stage_of[self.partition.segment(i)[0]] = i
        # unified telemetry plane: every layer's live stats object is a
        # view in ONE registry — router, gossip, relay (plus the derived
        # wire-byte total) and the composer's health counters — and the
        # per-stream ServeMetrics relay/control-plane fields fill from
        # its snapshot (_fill_stream_metrics), not from hand-written
        # mirroring per layer
        self.obs.expose("router", self.router.stats)
        if self.gossip is not None:
            self.obs.expose("gossip", self.gossip.stats)
            if self.gossip.relay is not None:
                rs = self.gossip.relay.stats
                self.obs.expose("relay", rs)
                self.obs.derived("relay/wire_bytes", rs.seeker_wire_bytes)
        if self._cp is not None:
            self.obs.expose("control_plane", self._cp.health)
        # end-to-end tracing (cfg.trace_enabled): one sim-clock tracer
        # shared by routing, serving, executors, gossip and relay, plus
        # an "rpc" scope on the composer's wall clock so control-plane
        # spans keep their own time domain in the same buffer. Disabled,
        # every site sees the shared NOOP_TRACER and pays one attribute
        # check — no allocation, no clock read.
        self.trace: Optional[TraceBuffer] = None
        self.tracer = NOOP_TRACER
        self._req_spans: Dict[int, object] = {}
        # the served path's own host work on the host clock
        # (set_host_tracer); NOOP_TRACER unless set
        self.host_tracer = NOOP_TRACER
        self._unhook_process_spans = None
        if self.gcfg.trace_enabled:
            self.trace = TraceBuffer(self.gcfg.trace_capacity)
            self.tracer = Tracer(self.trace, clock=lambda: self.bed.now,
                                 domain="serve")
            self.router.tracer = self.tracer
            if self.gossip is not None:
                self.gossip.tracer = self.tracer
                if self.gossip.relay is not None:
                    self.gossip.relay.tracer = self.tracer
            if self._cp is not None:
                self._cp.set_tracer(self.tracer.scope(
                    "rpc", clock=self._cp.clock.monotonic))
            self.set_host_tracer(self.tracer.scope(
                HOST_DOMAIN, clock=time.perf_counter))

    def set_host_tracer(self, tracer=None) -> None:
        """Attach a host-clock tracer to the served path: ``run_queue``'s
        window phases, each hop's stage dispatch and the router's DP,
        plus ``gc`` and ``compile`` process spans while it is set (see
        ``hook_process_spans``). Build it with ``annotate=True`` to see
        the spans in a ``jax.profiler`` trace beside the device's ops.
        ``None`` (or ``NOOP_TRACER``) clears it and removes the hooks."""
        tracer = NOOP_TRACER if tracer is None else tracer
        if self._unhook_process_spans is not None:
            self._unhook_process_spans()
            self._unhook_process_spans = None
        self.host_tracer = self.router.host_tracer = tracer
        if tracer.enabled:
            self._unhook_process_spans = hook_process_spans(tracer)

    # -- hop adapter -----------------------------------------------------------

    def _hop_fn(self, request_id: int, kv_tracked: bool = False):
        """Hop closure for one stream. With ``kv_tracked`` (the window
        loop) a hop is charged for the tokens it freshly processes —
        prefix length minus the peer's warm KV position — so warm chains
        decode at incremental cost while cold hops recompute. The default
        keeps ``generate``'s classic flat per-token charge."""
        def hop(peer_id: int, k: int, payload):
            peer = self.bed.peers[peer_id]
            if not self.bed.reachable(peer_id) or \
                    peer.fails_in_request(request_id, self.bed.rng):
                detect = self.gcfg.request_timeout_ms * 0.25
                return payload, detect, False
            stage = self._stage_of[peer.layer_start]
            ht = self.host_tracer
            with (ht.span("hop.dispatch", stage=stage) if ht.enabled
                  else NOOP_SPAN):
                out = self.stage_fns[stage](payload)   # REAL compute
            ntok = 1
            if kv_tracked:
                prefix = int(payload[0].shape[1])
                ntok = max(1, prefix - self.kv.warm_pos(request_id, peer_id))
                if ntok > 1:
                    # latency_est_ms means ONE decode step everywhere
                    # (routing costs, hedge triggers) — remember how to
                    # rescale this multi-token observation back to its
                    # single-token equivalent before the anchor EMA sees
                    # it, or a prefill chunk / cold recompute makes the
                    # charged peer look slow and routing ping-pongs
                    # between replicas, each flip paying a full-prefix
                    # recompute.
                    one = peer.compute_ms(1) + peer.net_delay_ms
                    full = peer.compute_ms(ntok) + peer.net_delay_ms
                    self._tok_scale[(request_id, peer_id)] = one / full
            return out, peer.hop_latency_ms(self.bed.rng, tokens=ntok), True

        return hop

    # -- route-table source ----------------------------------------------------

    def _sync_and_view(self):
        """Background sync tick + the table routing consumes this window:
        the gossip seeker's staleness-bounded ``routing_view`` when the
        sync plane is on, the classic in-process snapshot cache
        otherwise. Never a synchronous registry read on the request
        path either way."""
        now = self.bed.now
        if self.gossip is not None:
            self.gossip.maybe_tick(now)
            return self.sync_seeker.routing_view(now)
        self.seeker.maybe_sync(now)
        if self._cp is not None:
            # process backend: the sync above pulled the shard mirrors;
            # route on the composer's staleness-priced view so degraded
            # shards' rows are trust-discounted instead of trusted stale
            return self._cp.routing_view(now)
        return self.seeker.view()

    # -- serving ---------------------------------------------------------------

    def generate(self, prompt: np.ndarray, max_new_tokens: int,
                 request_id: int = 0, greedy: bool = True,
                 temperature: float = 1.0)\
            -> Tuple[np.ndarray, ServeMetrics]:
        tokens = jnp.asarray(prompt, jnp.int32)[None, :]
        metrics = ServeMetrics()
        t_start = self.bed.now
        route_fn = ALGORITHMS[self.algorithm]
        executor = ChainExecutor(self.gcfg, self._hop_fn(request_id))
        tr = self.tracer
        traced = tr.enabled
        rsp = None
        if traced:
            executor.tracer = tr
            rsp = tr.begin("request", cat="request", t0=t_start,
                           rid=request_id)

        for _ in range(max_new_tokens):
            table = self._sync_and_view()
            plan = None
            if self.algorithm == "gtrac":
                # planner path: K-best plan cached per snapshot version
                route, plan = plan_route(table, self.cfg.num_layers,
                                         self.gcfg, planner=self.planner)
            else:
                kwargs = ({"rng": self.bed.rng}
                          if self.algorithm == "naive" else {})
                route = route_fn(table, self.cfg.num_layers, self.gcfg,
                                 **kwargs)
            if not route.feasible:
                metrics.infeasible += 1
                break
            t_tok = self.bed.now
            report, payload = executor.execute(route.chain, table,
                                               payload=(tokens, None),
                                               plan=plan)
            for rep in split_reports(report):
                self.bed.anchor.apply_report(rep)
            metrics.repairs += int(report.repaired)
            metrics.rerouted += int(report.repaired)
            self.bed.advance(report.total_latency_ms / 1e3)
            if traced:
                ssp = tr.add("decode.step", t_tok, self.bed.now,
                             cat="decode", parent=rsp, rid=request_id,
                             emitted=report.success,
                             first_token=(report.success
                                          and metrics.ttft_ms < 0))
                self._trace_hops(ssp, t_tok, report)
            if not report.success:
                metrics.failures += 1
                break
            _, logits = payload
            if greedy:
                nxt = jnp.argmax(logits[:, -1, :], -1)
            else:
                tok = sample_token(logits[:, -1, :], self.bed.rng,
                                   temperature)
                nxt = jnp.full((tokens.shape[0],), tok, jnp.int32)
            tokens = jnp.concatenate([tokens, nxt[:, None].astype(jnp.int32)],
                                     axis=1)
            metrics.tokens += 1
            metrics.token_latency_ms.append(report.total_latency_ms)
            metrics.emit_ms.append(self.bed.now * 1e3)
            if metrics.ttft_ms < 0:
                metrics.ttft_ms = (self.bed.now - t_start) * 1e3
        self.bed.peers and [p.forget_request(request_id)
                            for p in self.bed.peers.values()]
        if traced:
            tr.end(rsp, t1=self.bed.now, ttft_ms=metrics.ttft_ms,
                   stale_rounds_max=metrics.stale_rounds_max)
        self._fill_stream_metrics(metrics)
        return np.asarray(tokens[0, len(prompt):]), metrics

    def _trace_hops(self, parent, t0: float, report) -> None:
        """Synthesize per-hop child spans under an exec span from the
        report's drawn latencies — hop latencies tile the step exactly
        (sum == total_latency_ms), so the serving hot path never reads
        the clock per hop."""
        tr = self.tracer
        t = t0
        for h in report.hops:
            t1 = t + h.latency_ms / 1e3
            tr.add("hop", t, t1, cat="exec", parent=parent,
                   peer=h.peer_id, ok=h.success)
            t = t1

    def _fill_stream_metrics(self, metrics: ServeMetrics) -> None:
        """Surface cumulative relay-plane / composer-health totals on a
        stream's metrics from ONE registry snapshot (``_STREAM_VIEW``).
        Fields whose owning layer is absent keep their defaults."""
        snap = self.obs.snapshot()
        for name, keys in _STREAM_VIEW:
            if all(k in snap for k in keys):
                setattr(metrics, name, sum(snap[k] for k in keys))

    def close(self) -> None:
        """Release control-plane resources (shard worker processes).
        Idempotent; a no-op for in-process registries."""
        fn = getattr(self.bed.anchor, "close", None)
        if fn is not None:
            fn()

    # -- window-batched serving (the batch router path) ------------------------

    def submit(self, spec, max_new_tokens: int = 16,
               tau: Optional[float] = None,
               request_id: Optional[int] = None) -> RoutedRequest:
        """Queue a stream for window-batched serving.

        ``spec`` is a ``repro.serving.api.SubmitSpec`` — the unified
        submission surface; its ``tau`` is this request's trust floor
        (row of the batched DP's tau vector, None = configured floor),
        ``arrival_time`` defers admission, ``kind`` pins the stream to
        the prefill/decode split under ``cfg.disaggregate``. Passing a
        raw prompt array with keywords is the deprecated pre-SubmitSpec
        form and forwards through a shim."""
        if not isinstance(spec, SubmitSpec):
            _deprecated_submit("GTRACPipelineServer")
            spec = SubmitSpec(prompt=spec, max_new_tokens=max_new_tokens,
                              tau=tau, request_id=request_id)
        rid = (self.admission.next_request_id()
               if spec.request_id is None else spec.request_id)
        req = RoutedRequest.from_spec(spec, rid)
        hop = self._hop_fn(rid, kv_tracked=True)
        # hedged window serving: behind cfg.hedge_enabled each stream runs
        # the hedging executor (fires a backup hop when the primary exceeds
        # hedge_quantile_factor x its latency estimate); plans splice
        # identically in both executors, so routing is unchanged
        req.executor = (HedgedChainExecutor(
            self.gcfg, hop,
            quantile_factor=self.gcfg.hedge_quantile_factor)
            if self.gcfg.hedge_enabled else ChainExecutor(self.gcfg, hop))
        if self.tracer.enabled:
            req.executor.tracer = self.tracer
        return self.admission.submit(req)

    def _emit_token(self, req: RoutedRequest, tok: int,
                    t_emit: float) -> None:
        """Append one generated token and stamp its emission time."""
        ht = self.host_tracer
        with (ht.span("emit") if ht.enabled else NOOP_SPAN):
            req.output.append(int(tok))
            req.metrics.tokens += 1
            req.metrics.emit_ms.append(t_emit * 1e3)
            if req.metrics.ttft_ms < 0:
                req.metrics.ttft_ms = (t_emit - req.arrival_time) * 1e3
            if (req.eos_id is not None and int(tok) == req.eos_id) or \
                    len(req.output) >= req.max_new_tokens:
                req.done = True

    def _finish_stream(self, req: RoutedRequest) -> None:
        """Stream left the pools: reclaim KV slots and failure draws."""
        rid = req.request_id
        self.kv.drop_stream(rid)
        for key in [k for k in self._tok_scale if k[0] == rid]:
            del self._tok_scale[key]
        for p in self.bed.peers.values():
            p.forget_request(rid)
        sp = self._req_spans.pop(rid, None)
        if sp is not None:
            self.tracer.end(sp, t1=self.bed.now,
                            ttft_ms=req.metrics.ttft_ms,
                            stale_rounds_max=req.metrics.stale_rounds_max)

    def _normalized_report(self, request_id: int, report):
        """Anchor-facing copy of ``report`` with every multi-token hop
        charge rescaled to its single-token equivalent. The wall latency
        (sim clock, TTFT/ITL stamps) keeps the real multi-token cost;
        only the trust plane's ``latency_est_ms`` EMA — whose unit is one
        decode step — is fed the normalized observation. Jitter survives:
        the rescale is a deterministic factor on the drawn latency."""
        hops, changed = [], False
        for h in report.hops:
            s = self._tok_scale.pop((request_id, h.peer_id), None)
            if s is not None and h.success:
                hops.append(HopReport(h.peer_id, h.latency_ms * s, True))
                changed = True
            else:
                hops.append(h)
        return replace(report, hops=hops) if changed else report

    def _apply_report(self, req: RoutedRequest, report) -> None:
        """Fold one chain execution's outcome into trust + metrics."""
        ht = self.host_tracer
        with (ht.span("trust_fold") if ht.enabled else NOOP_SPAN):
            anchor_rep = self._normalized_report(req.request_id, report)
            for rep in split_reports(anchor_rep):
                self.bed.anchor.apply_report(rep)
            req.metrics.repairs += int(report.repaired)
            req.metrics.rerouted += int(report.repaired)
            stats = getattr(req.executor, "stats", None)
            if stats is not None:         # hedged executor: surface counts
                req.metrics.hedges_fired = stats.hedges_fired
                req.metrics.hedges_won = stats.hedges_won

    def _pick(self, logits, ht) -> jax.Array:
        """The greedy token of one stream's chain, picked on the device;
        its copy to the host starts, and nothing waits for it."""
        with (ht.span("token_pick") if ht.enabled else NOOP_SPAN):
            tok = greedy_token(logits)
            tok.copy_to_host_async()
        return tok

    def _read_and_emit(self, picks, now: float, ht) -> None:
        """Read every token the window picked, in one device-to-host read,
        then emit the decode streams' tokens in the window's stream order
        at ``now`` plus their chains' latency. A final prefill chunk's
        first token is kept for its stream's promotion."""
        with (ht.span("token_sync") if ht.enabled else NOOP_SPAN):
            toks = jax.device_get([tok for _, tok, _ in picks])
        self._token_reads.inc()
        self._tokens_read.inc(len(picks))
        for (req, _, ms), tok in zip(picks, toks):
            tok = int(tok[0])
            if ms is None:
                req._pending_tok = tok
            else:
                req.metrics.token_latency_ms.append(ms)
                self._emit_token(req, tok, now + ms / 1e3)

    def run_queue(self) -> List[RoutedRequest]:
        """Serve every queued stream to completion under continuous
        window batching. Each window: one registry sweep (vectorized TTL
        / trust decay), one seeker sync check, one KV-locality
        validation, ONE batched device DP for all runnable streams'
        routes, then chain execution per stream.

        Decode streams run one token per window and advance the sim
        clock by the window's max decode-chain latency. Under
        ``cfg.disaggregate``, long-prompt streams instead prefill in
        dedicated chunked windows: each window launches at most the
        decode token budget (``cfg.router_max_batch`` tokens) of prefill
        chunks, a launched chunk occupies its stream until ``busy_until``
        (asynchronous — decode cadence is NOT stretched by prefill
        compute), and the final chunk's logits yield the first token, at
        which point the now-warm stream joins the decode pool. When
        nothing is runnable the clock jumps to the next chunk completion
        or pending arrival.

        With a host tracer set (``set_host_tracer``) the call is one
        ``run_queue`` span and each window a ``window`` span holding its
        phases: ``admit``, ``sync_view``, ``route`` (the DP itself is
        ``route.dp``), then per stream ``execute`` (one ``hop.dispatch``
        per stage call), ``trust_fold``, ``kv`` and ``token_pick`` (the
        greedy token picked on the device, its copy to the host started),
        then ``token_sync`` (the window's one device-to-host read of
        every token it picked), ``emit`` per stream that emitted, and
        last ``finish``. No token is read before ``token_sync``: the
        payloads carry host ids, and nothing that routes or folds trust
        reads a token's value."""
        ht = self.host_tracer
        with (ht.span("run_queue") if ht.enabled else NOOP_SPAN):
            served = self._serve_windows(ht)
            for req in served:
                self._fill_stream_metrics(req.metrics)
        return served

    def _serve_windows(self, ht) -> List[RoutedRequest]:
        """``run_queue``'s window loop, spanned on the host tracer
        ``ht``; returns the served streams."""
        served: List[RoutedRequest] = []
        active: List[RoutedRequest] = []      # decode pool
        prefill: List[RoutedRequest] = []     # dedicated prefill streams
        gcfg = self.gcfg
        tr = self.tracer
        traced = tr.enabled
        hon = ht.enabled
        while active or prefill or len(self.admission):
            with (ht.span("window") if hon else NOOP_SPAN):
                now = self.bed.now
                # admission sweeps the registry (per-shard fan-out when
                # the anchor is sharded) before the window is admitted
                with (ht.span("admit") if hon else NOOP_SPAN):
                    admitted = self.admission.next_window(
                        capacity=self.admission.max_batch - len(active)
                        - len(prefill), now=now)
                served += admitted
                if traced:
                    for req in admitted:
                        rsp = tr.begin("request", cat="request",
                                       t0=req.arrival_time,
                                       rid=req.request_id)
                        self._req_spans[req.request_id] = rsp
                        if now > req.arrival_time:
                            tr.add("queue.wait", req.arrival_time, now,
                                   cat="serve", parent=rsp,
                                   rid=req.request_id)
                if gcfg.disaggregate:
                    pre, dec = AdmissionQueue.split_by_kind(
                        admitted, gcfg.prefill_chunk_tokens)
                else:
                    pre, dec = [], admitted
                for req in pre:
                    req.busy_until = now
                prefill += pre
                active += dec
                # promote prefill streams whose final chunk has completed:
                # emit the pending first token (stamped at chunk
                # completion) and hand the warm stream to the decode pool
                waiting: List[RoutedRequest] = []
                for req in prefill:
                    if req.prefill_pos >= len(req.prompt) \
                            and req.busy_until <= now:
                        self._emit_token(req, req._pending_tok,
                                         req.busy_until)
                        if req.done:
                            self._finish_stream(req)
                        else:
                            active.append(req)
                    else:
                        waiting.append(req)
                prefill = waiting
                # launch prefill chunks up to the per-window token budget
                # — the decode token budget, so prefill can never claim
                # more window capacity than a full decode batch would.
                # The budget protects decode streams; when the decode
                # pool is empty there is nothing to displace, so every
                # runnable stream launches (chunk size stays capped at
                # the decode budget)
                budget = self.admission.max_batch if active else None
                chunks: List[Tuple[RoutedRequest, int]] = []
                for req in prefill:
                    if budget is not None and budget <= 0:
                        break
                    if req.busy_until > now:
                        continue               # chunk still in flight
                    c = min(gcfg.prefill_chunk_tokens,
                            len(req.prompt) - req.prefill_pos,
                            self.admission.max_batch)
                    if budget is not None:
                        c = min(c, budget)
                        budget -= c
                    chunks.append((req, c))
                if not active and not chunks:
                    # nothing runnable now: jump to the next chunk
                    # completion or the next arrival (bursty workloads)
                    targets = [r.busy_until for r in prefill]
                    nxt_arrival = self.admission.next_arrival()
                    if nxt_arrival is not None and nxt_arrival > now:
                        targets.append(nxt_arrival)
                    if not targets:
                        break
                    self.bed.advance(min(targets) - now)
                    continue
                wsp = (tr.begin("serve.window", cat="window", t0=now,
                                push=True, decode=len(active),
                                prefill_launches=len(chunks))
                       if traced else None)
                with (ht.span("sync_view") if hon else NOOP_SPAN):
                    table = self._sync_and_view()
                    self.kv.validate(table, gcfg.trust_floor)
                    stale_rounds = (int(self.sync_seeker.staleness_rounds(
                        self.bed.now).max())
                        if self.sync_seeker is not None else 0)
                with (ht.span("route") if hon else NOOP_SPAN):
                    for req in active + [r for r, _ in chunks]:
                        self.router.submit(
                            req.request_id, req.tau,
                            warm_ids=self.kv.warm_ids(req.request_id))
                        req.metrics.stale_rounds_max = max(
                            req.metrics.stale_rounds_max, stale_rounds)
                    plans = self.router.route_window(table)  # ONE DP
                # tokens picked on the device this window, read once at
                # its end: (stream, token, chain latency in ms; None for
                # a final prefill chunk's first token)
                picks: List[Tuple[RoutedRequest, jax.Array,
                                  Optional[float]]] = []
                # -- prefill chunk launches (asynchronous: charge
                #    busy_until, the decode window below does not wait
                #    for them) --------------------------------------------
                fail_ms = 0.0
                for req, c in chunks:
                    plan = plans[req.request_id]
                    if not plan.feasible:
                        req.metrics.infeasible += 1
                        req.done = True
                        continue
                    end = req.prefill_pos + c
                    prev_busy = req.busy_until
                    with (ht.span("execute") if hon else NOOP_SPAN):
                        report, out = req.executor.execute(
                            plan.chain_ids(0), table,
                            payload=(req.prompt[None, :end], None),
                            plan=plan)
                    self._apply_report(req, report)
                    if traced:
                        psp = self._req_spans.get(req.request_id)
                        if now - prev_busy > 1e-12:
                            # window-cadence gap between the previous
                            # chunk completing and this launch
                            tr.add("prefill.stall", prev_busy, now,
                                   cat="prefill", parent=psp,
                                   rid=req.request_id)
                        csp = tr.add("prefill.chunk", now,
                                     now + report.total_latency_ms / 1e3,
                                     cat="prefill", parent=psp,
                                     rid=req.request_id, tokens=c,
                                     ok=report.success)
                        self._trace_hops(csp, now, report)
                    if not report.success:
                        req.metrics.failures += 1
                        req.done = True
                        fail_ms = max(fail_ms, report.total_latency_ms)
                        continue
                    with (ht.span("kv") if hon else NOOP_SPAN):
                        self.kv.record(req.request_id, report.chain, end)
                    req.metrics.prefill_chunks += 1
                    req.metrics.prefill_tokens += c
                    req.prefill_pos = end
                    req.busy_until = now + report.total_latency_ms / 1e3
                    if end == len(req.prompt):
                        _, logits = out        # final chunk: first token
                        picks.append((req, self._pick(logits, ht), None))
                # -- decode window: one token per stream ----------------
                window_ms = 0.0
                w_spans: List[Tuple[object, float]] = []
                for req in active:
                    plan = plans[req.request_id]
                    if not plan.feasible:
                        req.metrics.infeasible += 1
                        req.done = True
                        continue
                    ids = req.ids()
                    prefix = ids.shape[1]
                    with (ht.span("execute") if hon else NOOP_SPAN):
                        report, payload = req.executor.execute(
                            plan.chain_ids(0), table,
                            payload=(ids, None), plan=plan)
                    self._apply_report(req, report)
                    window_ms = max(window_ms, report.total_latency_ms)
                    if traced:
                        ssp = tr.add(
                            "decode.step", now,
                            now + report.total_latency_ms / 1e3,
                            cat="decode",
                            parent=self._req_spans.get(req.request_id),
                            rid=req.request_id, emitted=report.success,
                            first_token=(report.success
                                         and req.metrics.ttft_ms < 0))
                        self._trace_hops(ssp, now, report)
                        w_spans.append((ssp, report.total_latency_ms))
                    if not report.success:
                        req.metrics.failures += 1
                        req.done = True
                        continue
                    # reuse accounting: only steps where the stream HAD
                    # warm KV somewhere count — a first-contact step
                    # (inline prefill, nothing recorded yet) is neither
                    # hit nor miss
                    with (ht.span("kv") if hon else NOOP_SPAN):
                        if self.kv.warm_ids(req.request_id):
                            if self.kv.chain_warm(req.request_id,
                                                  report.chain, prefix - 1):
                                req.metrics.kv_warm_hits += 1
                            else:
                                req.metrics.kv_cold_steps += 1
                        self.kv.record(req.request_id, report.chain, prefix)
                    _, logits = payload
                    picks.append((req, self._pick(logits, ht),
                                  report.total_latency_ms))
                if picks:
                    self._read_and_emit(picks, now, ht)
                # decode streams run concurrently: the clock advances by
                # the window's max decode latency; a pure-prefill window
                # advances to its earliest chunk completion instead
                if traced:
                    # drag: the batch-synchronization gap between a
                    # stream's own step finishing and the window's max
                    # latency — it delays the stream's NEXT token, so
                    # ITL_k+1 = exec_k+1 + drag_k (obs.report.
                    # itl_breakdown). Known only once the window closes,
                    # hence the late stamp.
                    for ssp, own in w_spans:
                        ssp.set(drag_ms=window_ms - own)
                if active:
                    self.bed.advance(window_ms / 1e3)
                elif chunks:
                    # ALL in-flight streams, not just this window's
                    # launches — an earlier chunk may complete (and
                    # promote) first
                    waits = [r.busy_until for r in prefill
                             if not r.done and r.busy_until > now]
                    self.bed.advance((min(waits) - now) if waits
                                     else fail_ms / 1e3)
                if traced:
                    tr.end(wsp, t1=self.bed.now, window_ms=window_ms)
                with (ht.span("finish") if hon else NOOP_SPAN):
                    for req in active:
                        if req.done:
                            self._finish_stream(req)
                    for req, _ in chunks:
                        if req.done:
                            self._finish_stream(req)
                active = [r for r in active if not r.done]
                prefill = [r for r in prefill if not r.done]
        return served
