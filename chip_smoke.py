"""Bring-up smoke: the routed pipeline serves full-width GPT-2 Large on one TPU.

    python chip_smoke.py [--seed N]

Run from the repository root; the script puts ``src`` on the path itself.
It drives the same objects as ``python -m repro.launch.serve --windowed``
(``build_model``, ``GTRACPipelineServer``, ``SubmitSpec``, ``run_queue``)
at GPT-2 Large's published width and depth (36 layers, d_model 1280, vocab
50,257, float32 parameters from ``--seed``), in five phases:

1. device: the first JAX device must be a TPU, and the window router's
   ``auto`` backend must resolve to the Pallas kernel. Anything else exits
   non-zero before any work is done; nothing runs on the CPU instead.
2. route kernel: on the served registry (108 peers, L = 36, K = 4), the
   compiled Pallas ``tropical_route_kbest`` equals ``layered_dp_kbest``
   bit for bit at R = 4 and R = 70, and ``plan_batched`` builds the same
   plans on the ``pallas`` and ``numpy`` backends.
3. golden: two fault-free replicas per stage (18 stages). All 4 requests
   emit 8 tokens, and each token is the greedy token of a monolithic
   ``model.prefill`` on the same prefix, or a reported near-tie.
4. mixed: the paper's replica mix (2 honeypot, 2 turtle, 2 golden per
   stage). The run must finish with coherent router counters; completion
   is reported, not required.
5. procs: the mixed run, shorter, with the anchor in two shard worker
   processes. No worker may map the TPU library or open the device.

Every line names the device. Wall times are host-clock observations of
one cold run, not benchmarks. The last line is one JSON object; it is
printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

N_REQUESTS, PROMPT_LEN, NEW_TOKENS = 4, 16, 8
PROCS_NEW_TOKENS = 4
LAYERS_PER_STAGE = 2
ROUTE_BATCHES = (4, 70)
# a differing token is accepted only as a near-tie: both logit vectors
# agree within NEAR_TIE_ULPS bf16 ulps of the largest logit, and the
# reference's top two logits are that close to each other
NEAR_TIE_ULPS = 8
BF16_EPS = 2.0 ** -8


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


class PhaseLog:
    """Counts backend compiles and persistent-cache hits, and prints one
    line per phase naming the device."""

    def __init__(self, jax):
        dev = jax.devices()[0]
        self.dev = dev
        self.tag = (f"[platform={dev.platform} device_kind={dev.device_kind} "
                    f"count={len(jax.devices())}]")
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, name, _secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @contextlib.contextmanager
    def phase(self, name):
        """Time a phase on the host clock; print its line if it passed.
        The body puts its one-line result under ``"result"``."""
        c0, h0, t0 = self.compiles, self.cache_hits, time.perf_counter()
        out = {}
        yield out
        wall = time.perf_counter() - t0
        stats = self.dev.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use", -1)
        print(f"{self.tag} {name}: PASS | {out.get('result', '')} | host "
              f"wall {wall:.3f} s (one cold run, not a benchmark) | "
              f"compiles {self.compiles - c0}, persistent-cache hits "
              f"{self.cache_hits - h0} | peak_bytes_in_use {peak}",
              flush=True)

    def note(self, msg):
        print(f"{self.tag} {msg}", flush=True)


# ---------------------------------------------------------------------------
# Phases. Each takes what it checks as arguments, so it can also be driven
# at a tiny size on the CPU (Pallas in interpret mode).
# ---------------------------------------------------------------------------


def route_kernel_phase(table, total_layers, gcfg, seed, *,
                       interpret=False):
    """Pallas K-best DP vs the jnp DP (bitwise), and plan_batched on
    pallas vs numpy, on the served topology with seeded integer latencies
    and trust on a 1/8 grid (exact in f32 and f64, tie-heavy)."""
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from repro.core.planner import RoutePlanner
    from repro.core.routing_jax import effective_costs, layered_dp_kbest
    from repro.kernels.tropical_route import tropical_route_kbest
    from repro.serving.batch_router import plan_batched

    rng = np.random.default_rng(seed)
    P, K = len(table), gcfg.k_best_routes
    table = dataclasses.replace(
        table,
        latency_ms=rng.integers(20, 400, P).astype(np.float64),
        trust=np.round(rng.uniform(0.75, 1.0, P) * 8) / 8,
        alive=rng.random(P) > 0.05)
    starts = jnp.asarray(table.layer_start, jnp.int32)
    ends = jnp.asarray(table.layer_end, jnp.int32)
    floors = np.array([0.0, 0.8, 0.9, gcfg.trust_floor, 1.0])
    out = []
    for R in ROUTE_BATCHES:
        taus = rng.choice(floors, R)
        costs = effective_costs(jnp.asarray(table.latency_ms, jnp.float32),
                                jnp.asarray(table.trust, jnp.float32),
                                jnp.asarray(table.alive),
                                jnp.asarray(taus, jnp.float32),
                                gcfg.request_timeout_ms)
        got = tropical_route_kbest(starts, ends, costs,
                                   total_layers=total_layers, k_best=K,
                                   interpret=interpret)
        want = layered_dp_kbest(starts, ends, costs,
                                total_layers=total_layers, k_best=K)
        for name, a, b in zip(("dist", "pedge", "prank"), got, want):
            a, b = np.asarray(a), np.asarray(b)
            check(a.shape == b.shape == (R, total_layers + 1, K),
                  f"R={R}: kernel {name} shape {a.shape} != {b.shape}")
            check(a.tobytes() == b.tobytes(),
                  f"R={R}: kernel {name} differs from layered_dp_kbest at "
                  f"{int(np.sum(a != b))}/{a.size} entries")
        plans = {}
        for backend in ("pallas", "numpy"):
            plans[backend] = plan_batched(
                table, total_layers, gcfg, taus,
                planner=RoutePlanner(total_layers, k_best=K), k_best=K,
                backend=backend, interpret=interpret)
        feasible = 0
        for r, (pp, pn) in enumerate(zip(plans["pallas"], plans["numpy"])):
            check(pp.chain_rows == pn.chain_rows,
                  f"R={R} row {r}: pallas plan {pp.chain_rows} != numpy "
                  f"plan {pn.chain_rows}")
            check(np.allclose(pp.costs, pn.costs, rtol=1e-6, atol=0.0),
                  f"R={R} row {r}: plan costs {pp.costs} != {pn.costs}")
            feasible += pp.feasible
        out.append(f"R={R}: dist/pedge/prank identical, {feasible}/{R} "
                   f"feasible plans equal numpy")
    return f"P={P} L={total_layers} K={K}; " + "; ".join(out)


def serve(srv, prompts, new_tokens):
    """Submit one request per prompt and run the window loop to the end."""
    from repro.serving.api import SubmitSpec

    for p in prompts:
        srv.submit(SubmitSpec(prompt=p, max_new_tokens=new_tokens))
    done = srv.run_queue()
    s = srv.router.stats
    check(s.device_calls + s.window_cache_hits == s.windows,
          f"router counters incoherent: {s}")
    check(len(done) == len(prompts), f"{len(done)}/{len(prompts)} served")
    return done


def golden_phase(srv, model, params, prompts, new_tokens):
    """Every request completes, and every served token is the monolithic
    model's greedy token on the served prefix (teacher-forced, so one
    accepted near-tie cannot cascade), or a near-tie within tolerance."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    done = serve(srv, prompts, new_tokens)
    prefill = jax.jit(lambda p, t: model.prefill(p, tokens=t)[0])

    def chain(tokens):
        payload = (tokens, None)
        for fn in srv.stage_fns:
            payload = fn(payload)
        return payload[1]

    near_ties, max_diff = 0, 0.0
    for req in sorted(done, key=lambda r: r.request_id):
        m = req.metrics
        check(m.tokens == new_tokens and m.failures == 0,
              f"request {req.request_id}: {m.tokens}/{new_tokens} tokens, "
              f"{m.failures} failures")
        seq = np.concatenate([req.prompt, np.asarray(req.output)])
        for t, tok in enumerate(req.output):
            prefix = jnp.asarray(seq[:len(req.prompt) + t], jnp.int32)[None]
            ref = np.asarray(prefill(params, prefix), np.float32).ravel()
            got = np.asarray(chain(prefix), np.float32).ravel()
            check(int(np.argmax(got)) == tok,
                  f"request {req.request_id} step {t}: served token {tok} "
                  f"is not the argmax of its own stage chain")
            diff = float(np.max(np.abs(got - ref)))
            max_diff = max(max_diff, diff)
            want = int(np.argmax(ref))
            if want == tok:
                continue
            tol = NEAR_TIE_ULPS * BF16_EPS * float(np.max(np.abs(ref)))
            top2 = np.sort(ref)[-2:]
            check(diff <= tol and top2[1] - top2[0] <= tol,
                  f"request {req.request_id} step {t}: served {tok}, "
                  f"monolithic {want}; |dlogit| {diff:.6g}, top-2 gap "
                  f"{top2[1] - top2[0]:.6g}, tolerance {tol:.6g}")
            near_ties += 1
    n = sum(len(r.output) for r in done)
    return (f"{len(done)}/{len(done)} requests x {new_tokens} tokens; "
            f"{n - near_ties}/{n} tokens equal monolithic greedy, "
            f"{near_ties} near-ties accepted (tolerance {NEAR_TIE_ULPS} bf16 "
            f"ulps of max |logit|); max |dlogit| {max_diff:.6g}; "
            f"{_router_line(srv)}")


def mixed_phase(srv, prompts, new_tokens):
    """The paper's replica mix: finish without exception, counters
    coherent; completion, SSR and repairs are reported."""
    from repro.serving.gtrac_serve import latency_summary

    done = serve(srv, prompts, new_tokens)
    ssr = sum(r.metrics.tokens == new_tokens for r in done)
    ls = latency_summary(done)
    repairs = sum(r.metrics.repairs for r in done)
    failures = sum(r.metrics.failures for r in done)
    return (f"completion {ls['completed']}/{ls['requests']} (emitted >= 1 "
            f"token), SSR {ssr}/{len(done)}, repairs {repairs}, failures "
            f"{failures}; {_router_line(srv)}")


def procs_phase(srv, prompts, new_tokens):
    """The mixed run with the anchor in shard worker processes started
    after this process holds the chip: none of them touches the TPU."""
    cp = srv._cp
    check(cp is not None, "control_plane='procs' built no process plane")
    pids = [ch.transport.proc.pid for ch in cp.channels]
    line = mixed_phase(srv, prompts, new_tokens)
    for pid in pids:
        check(not _holds_tpu(pid),
              f"shard worker {pid} maps libtpu or opens the device")
    h = cp.health
    return (f"{len(pids)} shard workers (pids {pids}) hold no TPU; "
            f"{line}; rpc retries {h.rpc_retries}, timeouts "
            f"{h.rpc_timeouts}, degraded windows {h.degraded_windows}")


def _holds_tpu(pid):
    """Whether process ``pid`` has libtpu mapped or a device node open."""
    with open(f"/proc/{pid}/maps") as f:
        if "libtpu" in f.read():
            return True
    fd_dir = f"/proc/{pid}/fd"
    for fd in os.listdir(fd_dir):
        try:
            target = os.readlink(os.path.join(fd_dir, fd))
        except OSError:
            continue
        if target.startswith(("/dev/accel", "/dev/vfio")):
            return True
    return False


def _router_line(srv):
    s = srv.router.stats
    return (f"router: {s.windows} windows, {s.device_calls} batched DP "
            f"calls, {s.window_cache_hits} window-cache hits")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, prompts and peer fleet")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.configs.base import GTRACConfig
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.api import build_model
    from repro.serving.batch_router import _resolve_backend
    from repro.serving.gtrac_serve import GTRACPipelineServer

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: the first JAX device is {dev.platform!r} "
              f"({dev.device_kind}), not a TPU; nothing was run",
              file=sys.stderr)
        return 1
    log = PhaseLog(jax)
    check(_resolve_backend("auto") == "pallas",
          "BatchRouter 'auto' does not resolve to the Pallas kernel")
    gcfg = GTRACConfig()
    check(gcfg.kv_reuse_bonus == 0.0,
          "kv_reuse_bonus > 0 would route warm windows on the host numpy "
          "DP instead of the device kernel")
    log.note(f"device: PASS | auto backend -> pallas | compile cache "
             f"{cache_dir}")

    cfg = dataclasses.replace(get_config("gpt2-large"), remat=False)
    model = build_model(cfg)
    with log.phase("init") as ph:
        params = jax.block_until_ready(
            jax.jit(model.init)(jax.random.PRNGKey(args.seed)))
        ph["result"] = (f"{cfg.name}: {cfg.num_layers} layers, d_model "
                        f"{cfg.d_model}, vocab {cfg.vocab_size}, "
                        f"{cfg.param_dtype} params")
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(1, cfg.vocab_size, (N_REQUESTS, PROMPT_LEN))

    mixed = GTRACPipelineServer(cfg, params, LAYERS_PER_STAGE,
                                gcfg=gcfg, seed=args.seed)
    with log.phase("route_kernel") as ph:
        ph["result"] = route_kernel_phase(mixed.bed.anchor.snapshot(0.0),
                                          cfg.num_layers, gcfg, args.seed)

    golden = GTRACPipelineServer(cfg, params, LAYERS_PER_STAGE,
                                 replicas={"golden": 2}, gcfg=gcfg,
                                 seed=args.seed)
    with log.phase("golden") as ph:
        ph["result"] = golden_phase(golden, model, params, prompts,
                                    NEW_TOKENS)
    del golden
    gc.collect()

    with log.phase("mixed") as ph:
        ph["result"] = mixed_phase(mixed, prompts, NEW_TOKENS)
    del mixed
    gc.collect()

    procs = GTRACPipelineServer(
        cfg, params, LAYERS_PER_STAGE, seed=args.seed,
        gcfg=dataclasses.replace(gcfg, control_plane="procs",
                                 anchor_shards=2))
    try:
        with log.phase("procs") as ph:
            ph["result"] = procs_phase(procs, prompts, PROCS_NEW_TOKENS)
    finally:
        procs.close()

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
