"""END-TO-END DRIVER: serve a small model with batched requests through the
full G-TRAC stack under adversarial peers.

This is the paper's system running for real: the model is layer-sharded
across simulated edge peers (honeypot / turtle / golden profiles), every
token window's chains are routed from the seeker's cached view in one
batched solve, hops execute REAL jitted stage computations, failures
trigger Bounded One-Shot Repair, and the Anchor learns trust from
execution reports.

    PYTHONPATH=src python examples/serve_gtrac.py [--requests 12] [--tokens 12]
"""
import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.models.api import build_model
from repro.serving.api import SubmitSpec
from repro.serving.gtrac_serve import GTRACPipelineServer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--tokens", type=int, default=12)
    ap.add_argument("--layers-per-stage", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config("gpt2-large").reduced(num_layers=8, vocab_size=512,
                                           remat=False)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    replicas = {"honeypot": 3, "turtle": 2, "golden": 2}

    print(f"model: {cfg.num_layers} layers, "
          f"{cfg.num_layers // args.layers_per_stage} pipeline stages, "
          f"peers/stage: {sum(replicas.values())} {replicas}")
    srv = GTRACPipelineServer(cfg, params,
                              layers_per_stage=args.layers_per_stage,
                              replicas=replicas, seed=args.seed)
    for _ in range(args.requests):
        srv.submit(SubmitSpec(prompt=rng.integers(1, cfg.vocab_size, size=8),
                              max_new_tokens=args.tokens))
    done = srv.run_queue()
    ok = sum(r.metrics.tokens == args.tokens for r in done)
    repairs = sum(r.metrics.repairs for r in done)
    failures = sum(r.metrics.failures for r in done)
    lats = [ms for r in done for ms in r.metrics.token_latency_ms]
    lat_s = np.mean(lats) / 1e3 if lats else float("nan")
    print(f"SSR {ok / args.requests:.2f}  {lat_s:.2f}s/token  "
          f"{repairs} repairs  {failures} failures  "
          f"{srv.router.stats.windows} windows")

    print("\nthe routing-policy comparison (gtrac vs sp / mr / naive / larac,"
          "\nno stage compute) is benchmarks/bench_ssr.py: at paper scale sp"
          "\nkeeps picking honeypots, SSR < 0.15 vs gtrac ~= 1.0.")


if __name__ == "__main__":
    main()
