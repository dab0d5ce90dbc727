"""Window-batched serving router overhead: per-token ``plan_route`` loop
vs ONE batched device DP per window (serving/batch_router.plan_batched),
at R ∈ {16, 64, 256} concurrent streams on the paper's 336-peer testbed,
plus end-to-end tokens/sec on the sim pipeline server.

Each request carries its own trust floor (the (R,) tau vector), so the
per-token baseline honestly pays one K-best numpy DP per request — the
regime the window router amortizes into a single compiled batched solve.
Both paths share the same warm ``RoutePlanner`` compiled snapshot.

Emits BENCH_serving.json via benchmarks/common and GATES the results
(exit 1 otherwise):
  * the batched path must beat the per-token loop by >= 3x at R = 64 on
    an unchanged registry;
  * disaggregated serving of a mixed long/short workload must hold
    decode p99 inter-token latency within 1.5x of the decode-only
    baseline while sustaining >= 0.8x the inline mixed run's prefill
    throughput (sim-time; the whole point of the dedicated prefill
    windows);
  * the KV-reuse lane must route > 0.8 of decode steps onto a fully
    warm chain under ``kv_reuse_bonus`` > 0, and at bonus 0 plans must
    be bit-identical with and without warm hints (no routing-parity
    regression);
  * the tracing-overhead lane (repro.obs): tracer-ENABLED windowed
    throughput must hold >= 0.95x the tracer-off run of the identical
    workload — a same-run ratio, so it is enforced in EVERY mode,
    --quick included; and (non-quick) the tracer-off windowed tok/s
    must stay >= 0.98x the previously recorded BENCH_serving.json
    value (the disabled path's one-attribute-check contract).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from benchmarks.common import emit, write_json
from repro.configs.base import GTRACConfig
from repro.core.planner import RoutePlanner, plan_route
from repro.serving.batch_router import BatchRouter, plan_batched
from repro.sim.testbed import build_paper_testbed

GATE_R = 64
GATE_X = 3.0
SIZES = (16, 64, 256)
GATE_ITL_X = 1.5          # disagg decode p99 ITL vs decode-only baseline
GATE_PREFILL_X = 0.8      # disagg prefill throughput vs inline mixed
GATE_WARM_RATE = 0.8      # warm-chain hit rate under kv_reuse_bonus
GATE_TRACE_ON_X = 0.95    # tracer-on windowed tok/s vs tracer-off, same run
GATE_TRACE_OFF_X = 0.98   # tracer-off windowed tok/s vs prior BENCH json


def _per_call_us(fn, reps: int) -> float:
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps * 1e6


def bench_routing_overhead(cfg: GTRACConfig, trials: int, seed: int,
                           sizes=SIZES):
    bed = build_paper_testbed(cfg=cfg, seed=seed)
    t = bed.anchor.snapshot(0.0)
    L = bed.total_layers
    planner = RoutePlanner(L, k_best=cfg.k_best_routes)
    planner.compile(t)          # warm: both paths route the same snapshot
    rng = np.random.default_rng(seed)
    speedups = {}
    for R in sizes:
        # distinct per-request floors: the per-token loop cannot collapse
        # them into one cached plan, exactly like per-request floors in
        # production (plan cache is version×tau keyed)
        taus = np.sort(rng.uniform(0.5, 0.9, R))

        def loop():
            for tau in taus:
                plan_route(t, L, cfg, tau=float(tau), planner=planner)

        def batched():
            plan_batched(t, L, cfg, taus, planner=planner,
                         k_best=cfg.k_best_routes)   # backend="auto"

        def batched_jnp():
            plan_batched(t, L, cfg, taus, planner=planner,
                         k_best=cfg.k_best_routes, backend="jnp")

        batched()               # warm-up
        batched_jnp()           # jit warm-up + device snapshot upload
        loop()
        reps = max(3, trials // 10)
        loop_us = _per_call_us(loop, reps) / R
        bat_us = _per_call_us(batched, reps) / R
        jnp_us = _per_call_us(batched_jnp, 3) / R
        speedups[R] = loop_us / bat_us
        emit(f"serving/per_token_loop/R{R}", loop_us,
             f"{loop_us:.1f}us_per_request")
        emit(f"serving/window_batched/R{R}", bat_us,
             f"{bat_us:.1f}us_per_request_{speedups[R]:.2f}x_vs_loop")
        # informational: the device DP path (the TPU-deploy backend; on
        # this CPU container it pays XLA loop/gather overhead)
        emit(f"serving/window_batched_jnp/R{R}", jnp_us,
             f"{jnp_us:.1f}us_per_request")
    return speedups


def bench_end_to_end(seed: int = 0):
    """Tokens/sec (wall clock) of the routed sim pipeline's
    window-batched ``run_queue``."""
    import jax
    from repro.configs import get_config
    from repro.models.api import build_model
    from repro.serving.api import SubmitSpec
    from repro.serving.gtrac_serve import GTRACPipelineServer

    cfg = get_config("gpt2-large").reduced(num_layers=4, vocab_size=128,
                                           remat=False)
    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    streams, tokens = 4, 6
    prompt = np.arange(1, 9)

    def serve(reps: int = 3) -> float:
        # warm-up compile pass, then best-of-reps on fresh servers (the
        # 24-token window is jax-dispatch dominated, so a single timed
        # shot scatters ~10% run to run)
        best = 0.0
        for rep in range(reps + 1):
            srv = GTRACPipelineServer(cfg, params, layers_per_stage=2,
                                      replicas={"golden": 2}, seed=seed)
            for _ in range(streams):
                srv.submit(SubmitSpec(prompt=prompt, max_new_tokens=tokens))
            t0 = time.perf_counter()
            done = srv.run_queue()
            dt = time.perf_counter() - t0
            if rep:
                best = max(best, sum(r.metrics.tokens for r in done) / dt)
        return best

    tps_win = serve()
    emit("serving/e2e/tokens_per_s/windowed", 1e6 / tps_win,
         f"{tps_win:.1f}tok_per_s")
    return {"windowed": round(tps_win, 2)}


def bench_trace_overhead(seed: int = 0, quick: bool = False):
    """Tracer-enabled vs tracer-disabled windowed serving of the
    IDENTICAL workload, wall clock. Both arms are best-of-N fresh
    servers after a shared jit warm-up, so the ratio isolates the
    instrumentation cost (span begin/end + post-hoc hop synthesis on,
    one ``tracer.enabled`` attribute check off)."""
    import jax
    from repro.configs import get_config
    from repro.models.api import build_model
    from repro.serving.api import SubmitSpec
    from repro.serving.gtrac_serve import GTRACPipelineServer

    layers = 2 if quick else 4
    cfg = get_config("gpt2-large").reduced(num_layers=layers,
                                           vocab_size=128, remat=False)
    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    streams, tokens = (2, 3) if quick else (4, 6)
    prompt = np.arange(1, 9)
    reps = 1 if quick else 3

    def tps(trace_enabled: bool) -> float:
        best = 0.0
        for _ in range(reps):
            srv = GTRACPipelineServer(
                cfg, params, layers_per_stage=layers // 2,
                replicas={"golden": 2},
                gcfg=GTRACConfig(trace_enabled=trace_enabled), seed=seed)
            for _ in range(streams):
                srv.submit(SubmitSpec(prompt=prompt,
                                      max_new_tokens=tokens))
            t0 = time.perf_counter()
            done = srv.run_queue()
            dt = time.perf_counter() - t0
            best = max(best, sum(r.metrics.tokens for r in done) / dt)
        return best

    tps(False)                   # shared jit warm-up pass
    off = tps(False)
    on = tps(True)
    ratio = on / off
    emit("serving/trace/tokens_per_s/off", 1e6 / off, f"{off:.1f}tok_per_s")
    emit("serving/trace/tokens_per_s/on", 1e6 / on,
         f"{on:.1f}tok_per_s_{ratio:.3f}x_vs_off")
    return {"off": round(off, 2), "on": round(on, 2),
            "ratio": round(ratio, 4)}


def bench_disaggregation(seed: int = 0, quick: bool = False):
    """Mixed long/short workload, sim-time latencies: decode-only
    baseline vs mixed inline vs mixed disaggregated. Sim latencies make
    this lane deterministic per seed — it measures the serving policy,
    not the host, so the gates are meaningful even in CI."""
    import jax
    from repro.configs import get_config
    from repro.models.api import build_model
    from repro.serving.gtrac_serve import GTRACPipelineServer, \
        latency_summary
    from repro.sim.workload import serving_workload

    layers = 2 if quick else 4
    cfg = get_config("gpt2-large").reduced(num_layers=layers,
                                           vocab_size=128, remat=False)
    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    n_req = 4 if quick else 12
    tokens = 2 if quick else 6
    long_len = 24 if quick else 96

    def serve(long_fraction: float, disaggregate: bool):
        # kv_reuse_bonus keeps chains sticky in every mode, so the
        # disagg-vs-inline comparison isolates the window policy
        gcfg = GTRACConfig(disaggregate=disaggregate,
                           prefill_chunk_tokens=16, kv_reuse_bonus=0.25)
        srv = GTRACPipelineServer(cfg, params, layers_per_stage=1,
                                  replicas={"golden": 2}, gcfg=gcfg,
                                  seed=seed)
        rng = np.random.default_rng(seed)
        for spec in serving_workload(rng, n_req,
                                     vocab_size=cfg.vocab_size,
                                     short_len=8, long_len=long_len,
                                     long_fraction=long_fraction,
                                     max_new_tokens=tokens):
            srv.submit(spec)
        done = srv.run_queue()
        ls = latency_summary(done)
        sim_s = max(srv.bed.now, 1e-9)
        # prompt tokens brought to first-token per sim second — inline
        # mode prefills inside the first decode step, so count prompts
        # of every stream that produced a token, not prefill_tokens
        pre_tok = sum(len(r.prompt) for r in done if r.metrics.tokens)
        return done, ls, pre_tok / sim_s

    _, base_ls, _ = serve(0.0, False)            # decode-only baseline
    _, inl_ls, inl_rate = serve(0.5, False)      # mixed, inline prefill
    dis_done, dis_ls, dis_rate = serve(0.5, True)   # mixed, disaggregated

    itl_ok = dis_ls["itl_p99_ms"] <= GATE_ITL_X * base_ls["itl_p99_ms"]
    pre_ok = dis_rate >= GATE_PREFILL_X * inl_rate
    warm = dis_ls["warm_hit_rate"]
    emit("serving/disagg/itl_p99_ms/decode_only", base_ls["itl_p99_ms"],
         f"{base_ls['itl_p99_ms']:.0f}ms")
    emit("serving/disagg/itl_p99_ms/mixed_inline", inl_ls["itl_p99_ms"],
         f"{inl_ls['itl_p99_ms']:.0f}ms")
    emit("serving/disagg/itl_p99_ms/mixed_disagg", dis_ls["itl_p99_ms"],
         f"{dis_ls['itl_p99_ms']:.0f}ms_vs_baseline_x"
         f"{dis_ls['itl_p99_ms'] / max(base_ls['itl_p99_ms'], 1e-9):.2f}")
    emit("serving/disagg/prefill_tok_per_s/inline", inl_rate,
         f"{inl_rate:.1f}tok_per_sim_s")
    emit("serving/disagg/prefill_tok_per_s/disagg", dis_rate,
         f"{dis_rate:.1f}tok_per_sim_s")
    emit("serving/disagg/warm_hit_rate", warm, f"{warm:.2f}")
    chunks = sum(r.metrics.prefill_chunks for r in dis_done)
    return {
        "itl_p99_ms": {"decode_only": round(base_ls["itl_p99_ms"], 1),
                       "mixed_inline": round(inl_ls["itl_p99_ms"], 1),
                       "mixed_disagg": round(dis_ls["itl_p99_ms"], 1)},
        "prefill_tok_per_sim_s": {"inline": round(inl_rate, 2),
                                  "disagg": round(dis_rate, 2)},
        "prefill_chunks": chunks,
        "warm_hit_rate": round(warm, 3),
        "gate_itl_1_5x": bool(itl_ok),
        "gate_prefill_0_8x": bool(pre_ok),
        "gate_warm_rate": bool(warm > GATE_WARM_RATE),
    }


def check_reuse_parity(cfg: GTRACConfig, seed: int = 0) -> bool:
    """kv_reuse_bonus=0 + warm hints must route bit-identically to no
    hints at all (the prefer-never-require contract's zero point)."""
    bed = build_paper_testbed(cfg=cfg, seed=seed)
    t = bed.anchor.snapshot(0.0)
    L = bed.total_layers
    rng = np.random.default_rng(seed)
    taus = rng.uniform(0.5, 0.9, 16)
    warm = [rng.choice(t.peer_ids, size=4, replace=False).tolist()
            for _ in range(len(taus))]

    def route(hints: bool):
        router = BatchRouter(planner=RoutePlanner(L, k_best=cfg.k_best_routes),
                             cfg=cfg, total_layers=L)
        for i, tau in enumerate(taus):
            router.submit(i, float(tau),
                          warm_ids=warm[i] if hints else None)
        return router.route_window(t)

    a, b = route(True), route(False)
    return all(a[i].chain_rows == b[i].chain_rows for i in range(len(taus)))


def run(trials: int = 50, seed: int = 0, quick: bool = False):
    """``quick`` is the CI smoke lane: R=8 only, no end-to-end model pass,
    and the >=3x perf gate is reported but NOT enforced (GitHub runners
    are too noisy to gate on; the gate runs on real hardware via
    ``make bench-serving``)."""
    cfg = GTRACConfig()
    sizes = (8,) if quick else SIZES
    speedups = bench_routing_overhead(cfg, trials, seed, sizes=sizes)
    e2e = None if quick else bench_end_to_end(seed)
    disagg = bench_disaggregation(seed, quick=quick)
    trace = bench_trace_overhead(seed, quick=quick)
    parity_ok = check_reuse_parity(cfg, seed)
    gate_r = sizes[-1] if quick else GATE_R
    gate_ok = speedups[gate_r] >= GATE_X
    # tracer-off regression: compare against the PREVIOUSLY tracked
    # measurement before this run overwrites it (non-quick only — the
    # quick lane writes its own file and runs on noisy CI hosts)
    prior_windowed = None
    if not quick and e2e is not None:
        try:
            with open("BENCH_serving.json") as f:
                prior_windowed = json.load(f).get(
                    "tokens_per_s", {}).get("windowed")
        except (OSError, ValueError):
            prior_windowed = None
    trace_on_ok = trace["ratio"] >= GATE_TRACE_ON_X
    trace_off_ok = (prior_windowed is None or e2e is None
                    or e2e["windowed"] >= GATE_TRACE_OFF_X * prior_windowed)
    emit("serving/gate", 0.0,
         f"batched_vs_loop_at_R{gate_r}:{speedups[gate_r]:.2f}x"
         f"(>= {GATE_X}x:{gate_ok}{'_UNENFORCED' if quick else ''})")
    emit("serving/gate_reuse_parity", 0.0, f"bonus0_parity:{parity_ok}")
    emit("serving/gate_trace_on", 0.0,
         f"tracer_on_vs_off:{trace['ratio']:.3f}x"
         f"(>= {GATE_TRACE_ON_X}x:{trace_on_ok})")
    extra = {"bench": "bench_serving", "trials": trials, "quick": quick,
             "speedup_loop_vs_batched": {
                 str(r): round(s, 3) for r, s in speedups.items()},
             "gate_r": gate_r, "gate_enforced": not quick,
             "disaggregation": disagg,
             "trace_overhead": trace,
             "gate_trace_on_0_95x": bool(trace_on_ok),
             "gate_reuse_parity": bool(parity_ok)}
    if not quick:
        # only the real measurement may claim the R=64 gate key
        extra["gate_R64_3x"] = bool(gate_ok)
        extra["gate_trace_off_0_98x"] = bool(trace_off_ok)
        if prior_windowed is not None:
            extra["trace_overhead"]["prior_windowed"] = prior_windowed
    if e2e is not None:
        extra["tokens_per_s"] = e2e
    # quick smoke runs must not clobber the tracked gated measurement
    write_json("BENCH_serving.quick.json" if quick else "BENCH_serving.json",
               prefix="serving/", extra=extra)
    failures = []
    if not gate_ok:
        failures.append(
            f"window-batched routing only {speedups[gate_r]:.2f}x vs "
            f"per-token loop at R={gate_r} (need >= {GATE_X}x)")
    if not disagg["gate_itl_1_5x"]:
        failures.append(
            f"disaggregated decode p99 ITL "
            f"{disagg['itl_p99_ms']['mixed_disagg']}ms exceeds "
            f"{GATE_ITL_X}x decode-only baseline "
            f"{disagg['itl_p99_ms']['decode_only']}ms")
    if not disagg["gate_prefill_0_8x"]:
        failures.append(
            f"disaggregated prefill throughput "
            f"{disagg['prefill_tok_per_sim_s']['disagg']} below "
            f"{GATE_PREFILL_X}x inline "
            f"{disagg['prefill_tok_per_sim_s']['inline']}")
    if not disagg["gate_warm_rate"]:
        failures.append(
            f"warm-chain hit rate {disagg['warm_hit_rate']} "
            f"<= {GATE_WARM_RATE} under kv_reuse_bonus")
    if not parity_ok:
        failures.append("kv_reuse_bonus=0 routing parity broken")
    if not trace_off_ok:
        failures.append(
            f"tracer-off windowed throughput {e2e['windowed']} tok/s "
            f"regressed below {GATE_TRACE_OFF_X}x the prior recorded "
            f"{prior_windowed} tok/s")
    # the trace-on ratio is a same-run comparison (noise-robust), so it
    # is enforced even in --quick smoke mode
    hard_failures = []
    if not trace_on_ok:
        hard_failures.append(
            f"tracer-enabled windowed throughput only "
            f"{trace['ratio']:.3f}x tracer-off "
            f"(need >= {GATE_TRACE_ON_X}x)")
    if failures and not quick:
        for f in failures:
            print(f"GATE FAILED: {f}", file=sys.stderr)
        sys.exit(1)
    if hard_failures:
        for f in hard_failures:
            print(f"GATE FAILED: {f}", file=sys.stderr)
        sys.exit(1)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: tiny R, no e2e model pass, perf gate "
                         "reported but not enforced")
    ap.add_argument("--trials", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    trials = args.trials if args.trials is not None else \
        (5 if args.quick else 50)
    run(trials=trials, seed=args.seed, quick=args.quick)


if __name__ == "__main__":
    main()
