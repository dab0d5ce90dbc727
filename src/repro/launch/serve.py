"""Serving launcher: plain batched engine or G-TRAC trust-routed pipeline.

    PYTHONPATH=src python -m repro.launch.serve --arch gpt2-large --reduced \
        --mode gtrac --algorithm gtrac --tokens 16
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import GTRACConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import build_model
from repro.serving.api import SubmitSpec
from repro.serving.engine import ServingEngine
from repro.serving.gtrac_serve import GTRACPipelineServer, latency_summary
from repro.sim.workload import serving_workload


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-large")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mode", default="gtrac", choices=["engine", "gtrac"])
    ap.add_argument("--algorithm", default="gtrac",
                    choices=["gtrac", "sp", "mr", "naive", "larac"])
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--layers-per-stage", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--windowed", action="store_true",
                    help="gtrac mode: serve all requests concurrently via "
                         "the window-batched router (one batched DP per "
                         "token window) instead of per-token routing")
    ap.add_argument("--disaggregate", action="store_true",
                    help="windowed serving: long prompts prefill in "
                         "dedicated chunked windows feeding the decode "
                         "pool instead of stalling it (requires "
                         "--windowed)")
    ap.add_argument("--prefill-chunk", type=int, default=None, metavar="T",
                    help="prefill chunk size in tokens, and the prompt-"
                         "length threshold above which a stream gets a "
                         "dedicated prefill lane (default: "
                         "GTRACConfig.prefill_chunk_tokens)")
    ap.add_argument("--kv-reuse-bonus", type=float, default=None,
                    metavar="B",
                    help="per-request edge-cost discount on peers holding "
                         "a stream's warm KV, 0..1 (routing prefers, "
                         "never requires, the warm chain; default: "
                         "GTRACConfig.kv_reuse_bonus)")
    ap.add_argument("--prompt-len", type=int, default=8,
                    help="short (interactive) prompt length")
    ap.add_argument("--long-prompt-len", type=int, default=96,
                    help="long prompt length for the prefill-heavy tail")
    ap.add_argument("--long-fraction", type=float, default=0.0,
                    help="fraction of requests carrying a long prompt "
                         "(0 = all short, the classic workload)")
    ap.add_argument("--burst-every", type=float, default=0.0, metavar="S",
                    help="windowed serving: arrivals come in bursts "
                         "spaced S sim-seconds apart (0 = all queued "
                         "up front)")
    ap.add_argument("--burst-size", type=int, default=4,
                    help="requests per arrival burst (with --burst-every)")
    ap.add_argument("--shards", type=int, default=1,
                    help="anchor registry shards (1 = monolithic; >1 "
                         "partitions peers across S AnchorRegistry shards "
                         "by stable peer-id hash with composed snapshots)")
    ap.add_argument("--shard-by", default="peer", choices=["peer", "layer"],
                    help="shard placement key: peer-id hash or layer-slot "
                         "affinity")
    ap.add_argument("--control-plane", default="inproc",
                    choices=["inproc", "procs"],
                    help="anchor shard backend: in-process registries, or "
                         "one worker PROCESS per shard behind the RPC "
                         "control plane (repro.control_plane) — deadlines, "
                         "bounded retries, degraded-shard serving")
    ap.add_argument("--cp-timeout", type=float, default=None, metavar="S",
                    help="per-attempt composer->worker RPC deadline in "
                         "seconds (default: GTRACConfig.cp_rpc_timeout_s)")
    ap.add_argument("--cp-retries", type=int, default=None, metavar="N",
                    help="RPC retries after the first deadline expiry "
                         "(default: GTRACConfig.cp_rpc_retries)")
    ap.add_argument("--cp-backoff", type=float, default=None, metavar="S",
                    help="base backoff before the first retry; doubles "
                         "per attempt (default: "
                         "GTRACConfig.cp_backoff_base_s)")
    ap.add_argument("--hedged", action="store_true",
                    help="hedged window serving: fire a backup hop when a "
                         "primary exceeds its latency-quantile trigger")
    ap.add_argument("--gossip", action="store_true",
                    help="route from a gossip-synced seeker cache "
                         "(repro.sync): anchors push per-shard version "
                         "vectors, the seeker pulls delta-encoded dirty "
                         "shards, and routing prices staleness instead of "
                         "reading in-process snapshots")
    ap.add_argument("--gossip-period", type=float, default=None,
                    metavar="S",
                    help="gossip round period in seconds "
                         "(default: T_gossip from GTRACConfig)")
    ap.add_argument("--gossip-fanout", type=int, default=2,
                    help="max dirty shards a seeker pulls per round "
                         "(the rest defer — bandwidth cap)")
    ap.add_argument("--gossip-stale-margin", type=float, default=0.0,
                    metavar="M",
                    help="trust docked per stale gossip round (an "
                         "inflated trust floor for shards the seeker "
                         "cannot confirm; 0 disables)")
    ap.add_argument("--gossip-stale-decay", type=float, default=0.0,
                    metavar="R",
                    help="seeker-side trust discount toward init_trust, "
                         "per second of shard staleness (0 disables)")
    ap.add_argument("--relay", action="store_true",
                    help="epidemic seeker->seeker relay (requires "
                         "--gossip): the anchor pushes only to "
                         "--gossip-fanout seed seekers per round and "
                         "the seekers relay delta chains to each other "
                         "— anchor cost O(fanout), convergence "
                         "O(log N) rounds")
    ap.add_argument("--relay-seekers", type=int, default=8, metavar="N",
                    help="seeker caches in the relay plane (routing "
                         "reads seeker 0; the rest carry the epidemic)")
    ap.add_argument("--relay-fanout", type=int, default=2,
                    help="neighbors each seeker pushes to per relay "
                         "round (seeded k-regular random sampling)")
    ap.add_argument("--relay-history", type=int, default=8,
                    help="per-shard delta chain depth a seeker retains "
                         "for forwarding (behind it: anti-entropy)")
    ap.add_argument("--relay-seed", type=int, default=0,
                    help="relay topology RNG seed (deterministic "
                         "per-round neighbor sampling)")
    ap.add_argument("--relay-blind", action="store_true",
                    help="disable the digest handshake: push whole "
                         "delta-chain messages to every neighbor "
                         "instead of summary/pull (the pre-handshake "
                         "wire protocol — more duplicate bytes)")
    ap.add_argument("--relay-no-verify", action="store_true",
                    help="disable digest verification, quarantine and "
                         "hb plausibility checks on relayed payloads "
                         "(trust every neighbor — the pre-hardening "
                         "behavior)")
    ap.add_argument("--relay-quarantine-rounds", type=int, default=None,
                    metavar="R",
                    help="relay rounds a convicted lying sender stays "
                         "quarantined per receiver (default: "
                         "GTRACConfig.relay_quarantine_rounds)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="gtrac mode: enable end-to-end tracing "
                         "(repro.obs), write the span trace to PATH and "
                         "print the per-request critical-path report")
    ap.add_argument("--trace-format", default="jsonl",
                    choices=["jsonl", "chrome"],
                    help="trace file format: JSONL span records, or a "
                         "Chrome trace-event file for chrome://tracing "
                         "/ Perfetto (default: jsonl)")
    args = ap.parse_args(argv)
    if args.windowed and args.algorithm != "gtrac":
        ap.error("--windowed routes via the gtrac batch router; "
                 "--algorithm %s is only available per-token" % args.algorithm)
    if args.hedged and not args.windowed:
        ap.error("--hedged is a window-serving feature (run_queue); "
                 "add --windowed — the per-token generate() path does "
                 "not hedge")
    if args.algorithm != "gtrac" and args.gossip:
        ap.error("--gossip serves from the trust-aware seeker cache; "
                 "--algorithm %s does not consume it" % args.algorithm)
    if args.relay and not args.gossip:
        ap.error("--relay rides on the gossip sync plane; add --gossip")
    if args.disaggregate and not args.windowed:
        ap.error("--disaggregate splits the window-batched serving loop "
                 "(run_queue); add --windowed")

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(num_layers=4)
    cfg = dataclasses.replace(cfg, remat=False)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)

    if args.mode == "engine":
        eng = ServingEngine(cfg, params)
        for _ in range(args.requests):
            prompt = rng.integers(1, cfg.vocab_size, size=args.prompt_len)
            eng.submit(SubmitSpec(prompt=prompt,
                                  max_new_tokens=args.tokens))
        done = eng.run_batch()
        for r in done:
            print(f"req {r.request_id}: {list(r.prompt)} -> {r.output}")
        return

    gossip_kw = {}
    if args.gossip_period is not None:
        gossip_kw["gossip_period_s"] = args.gossip_period
    if args.relay_quarantine_rounds is not None:
        gossip_kw["relay_quarantine_rounds"] = args.relay_quarantine_rounds
    if args.cp_timeout is not None:
        gossip_kw["cp_rpc_timeout_s"] = args.cp_timeout
    if args.cp_retries is not None:
        gossip_kw["cp_rpc_retries"] = args.cp_retries
    if args.cp_backoff is not None:
        gossip_kw["cp_backoff_base_s"] = args.cp_backoff
    if args.prefill_chunk is not None:
        gossip_kw["prefill_chunk_tokens"] = args.prefill_chunk
    if args.kv_reuse_bonus is not None:
        gossip_kw["kv_reuse_bonus"] = args.kv_reuse_bonus
    gcfg = GTRACConfig(anchor_shards=args.shards, shard_by=args.shard_by,
                       control_plane=args.control_plane,
                       disaggregate=args.disaggregate,
                       hedge_enabled=args.hedged,
                       gossip_enabled=args.gossip,
                       gossip_fanout=args.gossip_fanout,
                       gossip_stale_margin=args.gossip_stale_margin,
                       gossip_stale_decay=args.gossip_stale_decay,
                       relay_enabled=args.relay,
                       relay_fanout=args.relay_fanout,
                       relay_history=args.relay_history,
                       relay_seed=args.relay_seed,
                       relay_handshake=not args.relay_blind,
                       relay_verify=not args.relay_no_verify,
                       gossip_seekers=(args.relay_seekers if args.relay
                                       else 1),
                       trace_enabled=args.trace is not None,
                       **gossip_kw)
    srv = GTRACPipelineServer(cfg, params,
                              layers_per_stage=args.layers_per_stage,
                              algorithm=args.algorithm, seed=args.seed,
                              gcfg=gcfg)
    if args.windowed:
        for spec in serving_workload(
                rng, args.requests, vocab_size=cfg.vocab_size,
                short_len=args.prompt_len, long_len=args.long_prompt_len,
                long_fraction=args.long_fraction,
                max_new_tokens=args.tokens,
                burst_every_s=args.burst_every,
                burst_size=args.burst_size):
            srv.submit(spec)
        done = srv.run_queue()
        ok = 0
        for r in done:
            met = r.metrics
            ok += met.tokens == args.tokens
            print(f"req {r.request_id}: {met.tokens}/{args.tokens} tokens, "
                  f"{met.repairs} repairs, {met.failures} failures "
                  f"-> {r.output}")
        s = srv.router.stats
        hedges = sum(r.metrics.hedges_fired for r in done)
        print(f"SSR: {ok}/{args.requests}  windows: {s.windows}  "
              f"batched DP calls: {s.device_calls} "
              f"(vs {s.requests} per-token solves)  "
              f"anchor shards: {args.shards}  hedges fired: {hedges}")
        ls = latency_summary(done)
        chunks = sum(r.metrics.prefill_chunks for r in done)
        print(f"ttft p50/p99: {ls['ttft_p50_ms']:.0f}/"
              f"{ls['ttft_p99_ms']:.0f} ms  "
              f"itl p50/p99: {ls['itl_p50_ms']:.0f}/"
              f"{ls['itl_p99_ms']:.0f} ms  "
              f"kv warm-hit rate: {ls['warm_hit_rate']:.2f}  "
              f"prefill chunks: {chunks} "
              f"({'disaggregated' if args.disaggregate else 'inline'})")
        print(f"completion: {ls['completed']}/{ls['requests']} requests "
              f"emitted ({ls['incomplete']} incomplete, rate "
              f"{ls['completion_rate']:.2f})")
        if srv.gossip is not None:
            g = srv.gossip.stats
            stale = max((r.metrics.stale_rounds_max for r in done),
                        default=0)
            print(f"gossip: {g.rounds} rounds, {g.deltas} deltas "
                  f"({g.delta_bytes} B), {g.full_syncs} full syncs "
                  f"({g.full_bytes} B), max staleness {stale} rounds")
            if srv.gossip.relay is not None:
                rs = srv.gossip.relay.stats
                print(f"relay: {args.relay_seekers} seekers, "
                      f"{rs.msgs} msgs ({rs.msg_bytes} B), "
                      f"{rs.summaries} summaries ({rs.summary_bytes} B), "
                      f"{rs.deltas_applied} deltas applied, "
                      f"{rs.duplicates} duplicates, "
                      f"{rs.gaps} gaps ({rs.anchor_repairs} anchor / "
                      f"{rs.peer_full_syncs} peer repairs), "
                      f"anchor bytes {g.anchor_bytes()} B")
                print(f"relay hardening: {rs.digest_mismatches} digest "
                      f"mismatches, {rs.rejected_chains} rejected "
                      f"chains, {rs.quarantines} quarantines "
                      f"({rs.quarantine_drops} drops), "
                      f"{rs.hb_rejected} hb rejections")
        _report_control_plane(srv)
        _dump_trace(srv, args)
        srv.close()
        return
    ok = 0
    for rid in range(args.requests):
        prompt = rng.integers(1, cfg.vocab_size, size=args.prompt_len)
        out, met = srv.generate(prompt, max_new_tokens=args.tokens,
                                request_id=rid)
        ok += met.tokens == args.tokens
        lat = (np.mean(met.token_latency_ms) / 1e3
               if met.token_latency_ms else float("nan"))
        print(f"req {rid}: {met.tokens}/{args.tokens} tokens, "
              f"{met.repairs} repairs, {met.failures} failures, "
              f"{lat:.2f}s/token -> {list(out)}")
    print(f"SSR: {ok}/{args.requests}")
    _report_control_plane(srv)
    _dump_trace(srv, args)
    srv.close()


def _dump_trace(srv, args) -> None:
    """Export the run's span buffer and print the critical-path report
    (tracing runs only when --trace was passed)."""
    if getattr(srv, "trace", None) is None or not args.trace:
        return
    from repro.obs.export import export_chrome, export_jsonl
    from repro.obs.report import format_report
    if args.trace_format == "chrome":
        export_chrome(srv.trace, args.trace)
    else:
        export_jsonl(srv.trace, args.trace)
    print(f"trace: {len(srv.trace)} spans -> {args.trace} "
          f"({args.trace_format}, {srv.trace.dropped} evicted)")
    print(format_report(srv.trace))


def _report_control_plane(srv) -> None:
    """End-of-run health report for the process-backed control plane."""
    cp = getattr(srv, "_cp", None)
    if cp is None:
        return
    h = cp.health
    print(f"control plane: {cp.n_shards} worker procs, "
          f"{h.rpc_retries} rpc retries, {h.rpc_timeouts} timeouts, "
          f"{h.degraded_windows} degraded windows, "
          f"{h.worker_restarts} worker restarts, "
          f"{h.dropped_writes} dropped writes, "
          f"{h.full_resyncs} full resyncs")


if __name__ == "__main__":
    main()
