"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 chipbench/control.py --workload <cell> --seeds 1 2 3 ...

For each seed, in one process: the cell's set-up and one wave of its
traffic at its own load (the wave finishes the mix's longest requests),
then the comparison a run makes (``run.check``) twice: on what the
program served, and with the control in the program's place. The control
is the plain reference with every matmul operand and the residual stream
rounded to float8 e4m3, the type below the served bfloat16. It decodes
greedily each request that ``run.check`` samples, for as many tokens as
the program served it, and ``run.check`` judges its tokens as it judges
the program's. Each seed prints one JSON line with both verdicts and
readings; the last line gives the largest program reading and the
smallest control reading.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]

#: the type the control rounds to: the one below the served bfloat16
ROUNDING = "float8_e4m3fn"


def control_outcome(cell, out, seed: int):
    """``out`` with the control in the program's place: the requests that
    ``run.check`` samples from ``seed``, served by the reference at
    ``ROUNDING``."""
    import jax.numpy as jnp

    from chipbench.harness import cell as cells
    from chipbench.harness import correct

    cfg, mix = cell.config, cell.mix
    ref = cells.reference(cfg)
    picked = correct.sample(out.served, seed,
                            int(cfg["correct"]["sample_requests"]))
    length = int(mix["prompt_tokens"]["max"]) + int(mix["output_tokens"]["max"])
    params = ref.init_params(seed, cfg["model"])
    toks = np.zeros((len(picked), length), np.int32)
    for i, s in enumerate(picked):
        toks[i, :len(s.prompt)] = s.prompt
    last = np.array([len(s.prompt) - 1 for s in picked])
    rows = np.arange(len(picked))
    for t in range(max((s.max_new_tokens for s in picked), default=0)):
        pos = np.minimum(last + t, length - 1)
        lg = ref.logits(params, toks, cfg["model"], ROUNDING)
        pick = np.asarray(jnp.argmax(lg[rows, pos], -1))
        for i, s in enumerate(picked):
            if t < s.max_new_tokens:
                toks[i, pos[i] + 1] = pick[i]
    served = [correct.Served(s.prompt, toks[i, len(s.prompt):len(s.prompt)
                                            + s.max_new_tokens].tolist(),
                             s.max_new_tokens)
              for i, s in enumerate(picked)]
    return dataclasses.replace(out, served=served)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from chipbench import run as bench
    from chipbench.harness import cell as cells

    bench.configure_jax(jax)
    cell = cells.find(args.workload)
    program, control = [], []
    for seed in args.seeds:
        out = bench.serve(cell, seed, 0, False, t_start=time.perf_counter())
        checks, ok = bench.check(cell, out, seed)
        c_checks, c_ok = bench.check(cell, control_outcome(cell, out, seed),
                                     seed)
        program.append(checks["logit_gap"]["value"])
        control.append(c_checks["logit_gap"]["value"])
        print(json.dumps({"seed": seed,
                          "program": {"correct": ok, **{
                              k: c["value"] for k, c in checks.items()}},
                          "control": {"correct": c_ok, **{
                              k: c["value"] for k, c in c_checks.items()}},
                          "limits": {k: c["limit"]
                                     for k, c in checks.items()}}),
              flush=True)
    print(json.dumps({"workload": args.workload, "rounding": ROUNDING,
                      "seeds": len(args.seeds),
                      "program_gap_max": max(program),
                      "control_gap_min": min(control)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
