"""Chip benchmark of the trust-aware routed pipeline.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout that holds the program under ``src/``.
One run is one process on one machine with a TPU:

1. weights from ``--seed``, made on the device in one jitted call;
2. one ``GTRACPipelineServer`` for the cell's configuration;
3. warm-up: trust (where the deployment isolates peers first) and a walk
   through every token-sequence length the traffic can produce;
4. closed-loop waves of the cell's traffic until ``--seconds`` of host
   time have passed; the window ends with the last wave started in it;
5. the comparison that decides ``correct`` (``harness/correct.py``),
   after the window, with the program's state freed;
6. one JSON line on stdout: with ``--trace 0`` the cell's end-to-end
   metrics, with ``--trace 1`` its per-layer metrics, read from a profiler
   trace of the window's first wave.

It exits non-zero and prints no result when JAX finds no TPU, or fewer
chips than the cell asks for, or no program to run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
#: JAX's persistent compilation cache: a fixed directory of the checkout,
#: unless the environment names one
CACHE_DIR = ROOT / ".jax_cache"

EXIT_NO_PROGRAM, EXIT_NO_DEVICE = 2, 3


class NoDevice(RuntimeError):
    pass


def check_device(jax, chips: int):
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise NoDevice(f"the first JAX device is {dev.platform!r} "
                       f"({dev.device_kind}), not a TPU")
    if len(devs) < chips:
        raise NoDevice(f"{len(devs)} TPU chips, the cell asks for {chips}")
    return dev, len(devs)


def configure_jax(jax) -> None:
    """Persist every compiled program: in ``JAX_COMPILATION_CACHE_DIR``
    where it is set (JAX reads it itself), else in ``CACHE_DIR``."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


@dataclass
class Outcome:
    """What one run of a cell served, as plain host data; the program's
    state is gone by the time this exists."""
    device: dict
    peak: dict
    ctx: object                  # context.RunContext
    served: list                 # correct.Served per request of the window
    routes: list                 # serving.RouteRecord per routed window


def serve(cell, seed: int, seconds: float, trace: bool, *,
          require_tpu: bool = True, t_start: float = T_START,
          log=None) -> Outcome:
    """Set up, warm up and serve the window; then free the program."""
    import jax
    import numpy as np

    from chipbench.harness import cell as cells
    from chipbench.harness import correct, serving
    from chipbench.harness import trace as tracing
    from chipbench.harness.context import (
        RunContext,
        ServedRequest,
        WaveRecord,
    )
    from chipbench.harness.peaks import peaks
    from repro.models.api import build_model

    log = log or _stderr
    if require_tpu:
        dev, count = check_device(jax, cell.chips)
        peak = peaks(dev.device_kind)
    else:
        dev, count = jax.devices()[0], len(jax.devices())
        peak = peaks("TPU v5 lite")
    cfg, mix = cell.config, cell.mix
    model_cfg = cells.adapter(cfg).model_config(cfg)
    counter = serving.CompileCounter()
    try:
        stamps = [("device", time.perf_counter())]
        params = jax.block_until_ready(
            jax.jit(build_model(model_cfg).init)(jax.random.PRNGKey(seed)))
        stamps.append(("weights", time.perf_counter()))
        srv = serving.build_server(cfg, model_cfg, params)
        probe = serving.Probe(annotate=trace)
        probe.attach(srv)
        stamps.append(("server", time.perf_counter()))
        info = serving.warm_up(srv, probe, cfg, mix, seed,
                               model_cfg.vocab_size)
        stamps.append(("warm-up", time.perf_counter()))
        # set-up's garbage goes before the window; the collector keeps
        # the settings the program runs with
        gc.collect()
        compiles0, hits0 = counter.compiles, counter.cache_hits
        setup_s = time.perf_counter() - t_start
        trace_dir = tempfile.mkdtemp(prefix="chipbench-") if trace else None
        try:
            waves = serving.run_window(srv, probe, mix, seed,
                                       model_cfg.vocab_size, seconds,
                                       trace_dir)
            compiles = counter.compiles - compiles0
            stats = dev.memory_stats() or {}
            mem_peak = int(stats.get("peak_bytes_in_use", 0))
            reduced = (tracing.reduce(tracing.load(trace_dir))
                       if trace else None)
        finally:
            if trace_dir:
                shutil.rmtree(trace_dir, ignore_errors=True)
        t = t_start
        phases = []
        for name, stamp in stamps:
            phases.append(f"{name} {stamp - t:.1f} s")
            t = stamp
        log(f"set-up phases: {', '.join(phases)}")
        log(f"set-up {setup_s:.3f} s ({info['trust_waves']} trust waves, "
            f"{info['walks']} length walks; {compiles0} compiles, "
            f"{hits0} persistent-cache hits); window: {len(waves)} waves, "
            f"{compiles} compiles, {counter.cache_hits - hits0} cache hits; "
            f"peak_bytes_in_use {mem_peak}")
    finally:
        counter.close()

    t0, t1 = waves[0].t_due, waves[-1].t_end
    records = [WaveRecord(w.t_due, w.t_end, [
        ServedRequest(len(r.prompt), r.max_new_tokens, len(r.output),
                      probe.emits.get(r.request_id, []),
                      list(r.metrics.itl_ms()))
        for r in w.requests]) for w in waves]
    served = [correct.Served(np.asarray(r.prompt), list(r.output),
                             r.max_new_tokens)
              for w in waves for r in w.requests]
    routes = [rec for rec in probe.routes if t0 <= rec.t0 <= t1]
    L, K = model_cfg.num_layers, int(cfg["gtrac"]["k_best_routes"])
    ctx = RunContext(
        model=cfg["model"], waves=records,
        calls={k: [(a, b) for a, b in v if t0 <= a <= t1]
               for k, v in probe.calls.items()},
        route_calls=[(len(rec.plans), len(rec.starts), L, K, rec.device_call,
                      rec.t0) for rec in routes],
        compiles_in_window=compiles, setup_s=setup_s, peak=peak,
        trace=reduced, traced_wave=records[0] if trace else None)
    gaps = [(b - a) * 1e3 for r in ctx.requests
            for a, b in zip(r.emits, r.emits[1:])]
    if gaps:
        log(f"host token gaps: {len(gaps)}; p90 "
            f"{np.percentile(gaps, 90):.1f} ms, p99 "
            f"{np.percentile(gaps, 99):.1f} ms, longest {max(gaps):.1f} ms")
    log("waves (s): " + ", ".join(f"{w.t_end - w.t_due:.2f}" for w in records)
        + "; longest call (ms): " + ", ".join(
            f"{k} {max((b - a for a, b in v), default=0.0) * 1e3:.1f}"
            for k, v in ctx.calls.items()))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": count, "memory_peak_bytes": mem_peak}
    # the program's state goes before any reference runs
    del srv, params, waves, probe
    gc.collect()
    return Outcome(device, peak, ctx, served, routes)


def reference_gaps(cell, served, seed: int):
    """The served tokens' logit gaps against the plain reference, on the
    sample drawn from ``seed``."""
    from chipbench.harness import cell as cells
    from chipbench.harness import correct

    cfg, mix = cell.config, cell.mix
    ref = cells.reference(cfg)
    picked = correct.sample(served, seed, int(cfg["correct"]["sample_requests"]))
    length = int(mix["prompt_tokens"]["max"]) + int(mix["output_tokens"]["max"])
    tokens, mask = correct.batch(picked, length)
    params = ref.init_params(seed, cfg["model"])
    want = ref.logits(params, tokens, cfg["model"])
    return correct.served_gaps(want, tokens, mask), len(picked)


def check(cell, out: Outcome, seed: int, log=None):
    """The numbers that decide ``correct``, each with its limit."""
    from chipbench.harness import cell as cells
    from chipbench.harness import correct

    log = log or _stderr
    cfg = cell.config
    kbest = cells.load_module(cells.BENCH / "references" / "kbest.py",
                              "ref_kbest")
    bad_routes, first_bad = correct.route_mismatches(
        out.routes, cfg["gtrac"], int(cfg["model"]["n_layer"]), kbest)
    if first_bad:
        log(f"route mismatch: {first_bad}")
    gaps, n = reference_gaps(cell, out.served, seed)
    limit = float(cfg["correct"]["logit_gap_limit"])
    gap = float(gaps.max()) if gaps.size else -1.0
    log(f"compared {gaps.size} served tokens of {n} requests and "
        f"{len(out.routes)} routed windows")
    checks = {"logit_gap": {"value": gap, "limit": limit},
              "route_mismatches": {"value": bad_routes, "limit": 0}}
    ok = gaps.size > 0 and gap <= limit and bad_routes == 0
    return checks, bool(ok)


def result(cell, out: Outcome, checks: dict, ok: bool, trace: bool,
           log=None) -> dict:
    """The result line: the cell's metrics for this kind of run."""
    from chipbench.harness import cell as cells
    from chipbench.harness import trace as tracing

    log = log or _stderr
    ctx = out.ctx
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cells.metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    line = {"correct": ok, "attempted": len(ctx.requests),
            "failed": sum(r.tokens < r.max_new_tokens for r in ctx.requests),
            "metrics": metrics, "device": dict(out.device)}
    if trace and ctx.trace is not None:
        line["device"]["busy_s"] = ctx.trace.busy_s
        line["device"]["window_s"] = ctx.trace.window_s
        line["breakdown"] = tracing.breakdown(ctx.trace)
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    line["checks"] = checks
    return line


def run(cell, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, t_start: float = T_START,
        log=None) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    out = serve(cell, seed, seconds, trace, require_tpu=require_tpu,
                t_start=t_start, log=log)
    checks, ok = check(cell, out, seed, log=log)
    return result(cell, out, checks, ok, trace, log=log)


def _stderr(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: no program under {ROOT / 'src'}", file=sys.stderr)
        return EXIT_NO_PROGRAM
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from chipbench.harness import cell as cells

    try:
        cell = cells.find(args.workload)
    except cells.CellError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return EXIT_NO_PROGRAM

    # libtpu logs under /tmp unless told otherwise; a run writes only
    # inside its checkout and its own temporary directory
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    configure_jax(jax)
    try:
        out = run(cell, args.seed, args.seconds, bool(args.trace))
    except NoDevice as e:
        print(f"chipbench: {e}; nothing was run", file=sys.stderr)
        return EXIT_NO_DEVICE
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
