"""Drive ``GTRACPipelineServer.run_queue`` in closed-loop waves, with host
stamps taken at the server's boundaries from outside the program.

``Probe`` wraps, on the server instance after it is built, the calls that
mark each layer: ``admission.next_window`` (admission and registry sweep),
``router.route_window`` (routing), each request's ``executor.execute``
(chain execution) and ``_emit_token`` (a token the host has synced: the
server calls it after ``int(jnp.argmax(...))``). A wrapper records
``time.perf_counter()`` around the call and, in a traced run, a
``jax.profiler.TraceAnnotation`` span; it changes no argument and no
result. ``_emit_token`` is private: the wrapper goes once the program has
host-clock spans of its own.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from chipbench.harness import traffic

#: warm-up waves allowed for the registry to isolate the untrusted peers
MAX_TRUST_WAVES = 64
#: tokens per request in those waves
TRUST_WAVE_TOKENS = 2
#: attempts of the length walk (a walk stream can fail on a faulty peer)
MAX_WALKS = 4

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SetupError(RuntimeError):
    """Set-up could not bring the server to the state the cell needs."""


class CompileCounter:
    """Counts backend compiles and persistent-cache hits from JAX's
    monitoring events, as ``chip_smoke.PhaseLog`` does."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, name, _secs, **_kw):
        if name == COMPILE_EVENT:
            self.compiles += 1

    def _on_event(self, name, **_kw):
        if name == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def close(self):
        m = self._jax.monitoring
        m.unregister_event_duration_listener(self._on_dur)
        m.unregister_event_listener(self._on_event)


@dataclass
class RouteRecord:
    """One ``route_window`` call: the table it routed on and its plans."""
    starts: np.ndarray
    ends: np.ndarray
    latency_ms: np.ndarray
    trust: np.ndarray
    alive: np.ndarray
    plans: List[Tuple[float, List[List[int]], List[float]]]  # per floor
    device_call: bool
    t0: float


@dataclass
class Probe:
    """Host stamps at the server's layer boundaries."""
    annotate: bool = False
    calls: Dict[str, List[Tuple[float, float]]] = field(
        default_factory=lambda: {"admit": [], "route": [], "chain": []})
    emits: Dict[int, List[float]] = field(default_factory=dict)
    routes: List[RouteRecord] = field(default_factory=list)
    floor: float = 0.0           # the server's configured trust floor

    def span(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"chipbench.{name}")

    def timed(self, fn: Callable, kind: str) -> Callable:
        log = self.calls[kind]
        span = self.span

        def call(*args, **kwargs):
            with span(kind):
                t0 = time.perf_counter()
                out = fn(*args, **kwargs)
                log.append((t0, time.perf_counter()))
            return out

        return call

    def attach(self, srv) -> None:
        self.floor = float(srv.gcfg.trust_floor)
        srv.admission.next_window = self.timed(srv.admission.next_window,
                                               "admit")
        route = srv.router.route_window
        stats = srv.router.stats
        span = self.span

        def route_window(table):
            calls = stats.device_calls
            with span("route"):
                t0 = time.perf_counter()
                plans = route(table)
                t1 = time.perf_counter()
            self._record_route(table, plans, stats.device_calls > calls,
                               t0, t1)
            return plans

        srv.router.route_window = route_window
        emit = srv._emit_token
        emits = self.emits

        def emit_token(req, tok, t_emit):
            emits.setdefault(req.request_id, []).append(time.perf_counter())
            with span("emit"):
                emit(req, tok, t_emit)

        srv._emit_token = emit_token

    def _record_route(self, table, plans, device_call, t0, t1) -> None:
        self.calls["route"].append((t0, t1))
        # requests at one floor share one plan object
        by_plan = {id(plan): plan for plan in plans.values()}
        self.routes.append(RouteRecord(
            starts=np.array(table.layer_start), ends=np.array(table.layer_end),
            latency_ms=np.array(table.latency_ms),
            trust=np.array(table.trust), alive=np.array(table.alive),
            plans=[(self.floor, [list(map(int, c)) for c in p.chain_rows],
                    [float(c) for c in p.costs])
                   for p in by_plan.values()],
            device_call=device_call, t0=t0))


@dataclass
class Wave:
    t_due: float                 # host stamp of the run_queue call
    t_end: float
    requests: list               # the served RoutedRequest objects


def serve_wave(srv, probe: Probe, specs: List[traffic.RequestSpec]) -> Wave:
    """Submit every request at once and drain the queue."""
    from repro.serving.api import SubmitSpec

    reqs = []
    for s in specs:
        req = srv.submit(SubmitSpec(prompt=s.prompt,
                                    max_new_tokens=s.max_new_tokens,
                                    arrival_time=srv.bed.now))
        req.executor.execute = probe.timed(req.executor.execute, "chain")
        reqs.append(req)
    with probe.span("wave"):
        t_due = time.perf_counter()
        srv.run_queue()
        t_end = time.perf_counter()
    return Wave(t_due, t_end, reqs)


def build_server(cfg: dict, model_cfg, params):
    """One ``GTRACPipelineServer`` for the configuration's deployment.
    The fleet (peer profiles, delays, failure odds) and the sim clock's
    draws come from the deployment's own ``fleet_seed``."""
    from repro.configs.base import GTRACConfig
    from repro.serving.gtrac_serve import GTRACPipelineServer

    dep = cfg["deployment"]
    return GTRACPipelineServer(model_cfg, params, int(dep["layers_per_stage"]),
                               replicas=dict(dep["replicas"]),
                               gcfg=GTRACConfig(**cfg["gtrac"]),
                               seed=int(dep["fleet_seed"]))


def untrusted_left(srv, profiles) -> int:
    """Peers of ``profiles`` still at or above the trust floor."""
    if not profiles:
        return 0
    table = srv.bed.anchor.snapshot(srv.bed.now)
    trust = dict(zip(map(int, table.peer_ids), table.trust))
    return sum(trust[p.peer_id] >= srv.gcfg.trust_floor
               for name in profiles for p in srv.bed.peers_by_profile(name))


def warm_up(srv, probe: Probe, cfg: dict, mix: dict, seed: int,
            vocab: int) -> dict:
    """Bring the server to the state the window starts from.

    1. Where the deployment names profiles to isolate, serve short waves
       of the mix until the registry holds every such peer below the
       trust floor.
    2. Walk every token-sequence length the mix can produce through the
       served path, so no program compiles inside the window.
    """
    isolate = cfg["deployment"].get("isolate_before_window", [])
    trust_waves = 0
    while untrusted_left(srv, isolate):
        if trust_waves == MAX_TRUST_WAVES:
            raise SetupError(f"{untrusted_left(srv, isolate)} peers of "
                             f"{isolate} still trusted after {trust_waves} "
                             f"warm-up waves")
        serve_wave(srv, probe, traffic.wave(
            mix, seed, trust_waves, vocab, stream=traffic.WARMUP,
            max_new_tokens=TRUST_WAVE_TOKENS))
        trust_waves += 1
    need = set(traffic.prefix_lengths(mix))
    walks = 0
    while need:
        if walks == MAX_WALKS:
            raise SetupError(f"prefix lengths {sorted(need)} never served")
        w = serve_wave(srv, probe, traffic.length_walk(mix, seed + walks,
                                                       vocab))
        for req in w.requests:
            n = len(req.prompt)
            need -= set(range(n, n + len(req.output)))
        walks += 1
    return {"trust_waves": trust_waves, "walks": walks}


def run_window(srv, probe: Probe, mix: dict, seed: int, vocab: int,
               seconds: float, trace_dir: Optional[str]) -> List[Wave]:
    """Serve waves until ``seconds`` of host time have passed; the window
    ends with the last wave that started inside it. With ``trace_dir``
    the first wave runs under the profiler."""
    import jax

    waves: List[Wave] = []
    t0 = time.perf_counter()
    while not waves or time.perf_counter() - t0 < seconds:
        specs = traffic.wave(mix, seed, len(waves), vocab)
        if trace_dir is not None and not waves:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            try:
                waves.append(serve_wave(srv, probe, specs))
            finally:
                jax.profiler.stop_trace()
        else:
            waves.append(serve_wave(srv, probe, specs))
    return waves
