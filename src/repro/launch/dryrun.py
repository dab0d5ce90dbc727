import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the production mesh ((16,16) single-pod or
(2,16,16) multi-pod), constructs the appropriate step function
(train_step / prefill / serve_step) with ShapeDtypeStruct inputs (no
allocation), pins in/out shardings from distributed/sharding.py, and runs
``.lower().compile()``. Success proves the distribution config is coherent;
``memory_analysis`` + ``cost_analysis`` + the compiled HLO feed the roofline
(§Roofline in EXPERIMENTS.md).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun.jsonl
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (ALL_ARCHS, ASSIGNED_ARCHS, SHAPES, get_config,
                           get_shape, shape_applicable)
from repro.configs.base import ShapeConfig, TrainConfig
from repro.distributed import sharding as sh
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.models.api import build_model
from repro.trainer import optimizer as opt
from repro.trainer.train_loop import make_train_step


def _named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def build_lowerable(arch: str, shape: ShapeConfig, mesh,
                    overrides: Dict[str, Any] = None,
                    serving_layout: bool = False):
    """Returns (jitted fn, example args (ShapeDtypeStructs)).

    scan_layers=False (unrolled lowering): XLA's HLO cost analysis counts
    while-loop bodies once, so a scanned layer stack would under-report
    flops/bytes/collectives by ~num_layers× (verified empirically). The
    unrolled HLO carries the true totals; on-device execution would use the
    scanned form (identical math, smaller program).
    """
    kw = {"scan_layers": False}
    kw.update(overrides or {})
    microbatches = int(kw.pop("__microbatches__", 1))
    cfg = dataclasses.replace(get_config(arch), **kw)
    model = build_model(cfg)
    params_spec = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))

    def named(spec_tree, aval_tree):
        """fit (divisibility) + wrap in NamedShardings."""
        return _named(mesh, sh.fit_pspecs(mesh, spec_tree, aval_tree))

    p_pspec = sh.param_pspecs(params_spec, serving=serving_layout)
    p_shard = named(p_pspec, params_spec)

    if shape.kind == "train":
        tcfg = TrainConfig(microbatches=microbatches)
        step = make_train_step(model, tcfg,
                               unroll_accum=not cfg.scan_layers)
        opt_spec = jax.eval_shape(opt.init, params_spec)
        o_pspec = {"mu": sh.param_pspecs(params_spec),
                   "nu": sh.param_pspecs(params_spec), "step": P()}
        o_shard = named(o_pspec, opt_spec)
        batch = model.input_specs(shape)
        b_shard = named(sh.batch_pspecs(mesh, batch), batch)
        metrics_shard = _named(mesh, {"loss": P(), "lr": P(),
                                      "grad_norm": P()})
        fn = jax.jit(step,
                     in_shardings=(p_shard, o_shard, b_shard),
                     out_shardings=(p_shard, o_shard, metrics_shard))
        return fn, (params_spec, opt_spec, batch)

    if shape.kind == "prefill":
        inputs = model.input_specs(shape)

        def prefill_fn(params, inputs):
            return model.prefill(params, **inputs)

        out_spec = jax.eval_shape(prefill_fn, params_spec, inputs)
        logits_sh = named(sh.logits_pspec(mesh), out_spec[0])
        cache_sh = named(sh.cache_pspecs(mesh, cfg, out_spec[1]),
                         out_spec[1])
        i_shard = named(sh.batch_pspecs(mesh, inputs), inputs)
        fn = jax.jit(prefill_fn,
                     in_shardings=(p_shard, i_shard),
                     out_shardings=(logits_sh, cache_sh))
        return fn, (params_spec, inputs)

    # decode / serve_step
    token, cache = model.input_specs(shape)
    cache_sh = named(sh.cache_pspecs(mesh, cfg, cache), cache)
    tok_shard = named(sh.batch_pspecs(mesh, {"token": token})["token"],
                      token)
    batch_ok = shape.global_batch >= 16
    logits_aval = jax.ShapeDtypeStruct(
        (shape.global_batch, 1, cfg.vocab_size),
        jnp.dtype(cfg.logits_dtype))
    logits_sh = named(sh.logits_pspec(mesh, batch_ok), logits_aval)

    def serve_step(params, token, cache):
        return model.decode_step(params, token, cache)

    fn = jax.jit(serve_step,
                 in_shardings=(p_shard, tok_shard, cache_sh),
                 out_shardings=(logits_sh, cache_sh))
    return fn, (params_spec, token, cache)


#: unrolled-compile budget: archs deeper than this use two-point affine
#: extrapolation in layer count for the cost pass (exact for the
#: layer-homogeneous stacks here; the scanned pass is always full-depth)
UNROLL_MAX_LAYERS = 32


def _hybrid_period(cfg) -> int:
    """Layers between a hybrid model's first two hybrid layers."""
    ids = cfg.hybrid_layer_ids
    return ids[1] - ids[0] if len(ids) > 1 else cfg.num_layers


def _layer_scale_overrides(cfg, l: int) -> Dict[str, Any]:
    if cfg.family == "hybrid":  # the hybrid layers that fall in the cut
        return {"num_layers": l, "hybrid_layer_ids": tuple(
            i for i in cfg.hybrid_layer_ids if i < l)}
    if cfg.is_encoder_decoder:  # enc and dec scale together
        return {"num_layers": l, "enc_layers": l}
    return {"num_layers": l}


def _layer_count(cfg, overrides) -> float:
    return overrides.get("num_layers", cfg.num_layers)


def _cost_pass(arch, shape, mesh, base_overrides=None,
               serving_layout=False):
    """Compile the unrolled accounting program; extrapolate for deep nets.

    flops / bytes / per-kind collective wire bytes are affine in the layer
    (or group) count for every family here: total(L) = fixed + L * per_layer.
    Deep archs compile at two shallow depths and extrapolate to full depth.
    """
    clean = {k: v for k, v in (base_overrides or {}).items()
             if not k.startswith("__")}
    cfg = dataclasses.replace(get_config(arch), **clean)

    def compile_costs(overrides):
        merged = dict(base_overrides or {})
        merged.update(overrides)
        fn, args = build_lowerable(arch, shape, mesh, overrides=merged,
                                   serving_layout=serving_layout)
        compiled = fn.lower(*args).compile()
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        wires = rl.collective_wire_bytes(compiled.as_text())
        out = {"flops": float(cost.get("flops", 0.0)),
               "bytes accessed": float(cost.get("bytes accessed", 0.0))}
        for k, v in wires.items():
            out[f"wire/{k}"] = float(v)
        return out

    full_layers = cfg.num_layers
    deep = cfg.num_layers > UNROLL_MAX_LAYERS or \
        (cfg.is_encoder_decoder and cfg.num_layers + cfg.enc_layers >
         UNROLL_MAX_LAYERS)
    if not deep:
        return compile_costs({}), {"accounting": "full_unroll"}
    if cfg.family == "hybrid":
        l1, l2 = 2 * _hybrid_period(cfg), 4 * _hybrid_period(cfg)
    else:
        l1, l2 = 8, 16
    o1, o2 = _layer_scale_overrides(cfg, l1), _layer_scale_overrides(cfg, l2)
    c1 = compile_costs(o1)
    c2 = compile_costs(o2)
    n1, n2 = _layer_count(cfg, o1), _layer_count(cfg, o2)
    out = {}
    for k in c1:
        slope = (c2[k] - c1[k]) / (n2 - n1)
        out[k] = c1[k] + slope * (full_layers - n1)
    meta = {"accounting": f"affine_extrapolated(L{int(n1)},L{int(n2)})"}
    return out, meta


def run_cell(arch: str, shape_name: str, mesh_name: str,
             verbose: bool = True, cost_pass: bool = None,
             overrides: Dict[str, Any] = None, serving_layout: bool = False,
             tag: str = "") -> Dict[str, Any]:
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name,
                           "num_devices": mesh.devices.size}
    if tag:
        rec["tag"] = tag
    if overrides:
        rec["overrides"] = {k: str(v) for k, v in overrides.items()}
    if serving_layout:
        rec["serving_layout"] = True
    if cost_pass is None:  # roofline table is single-pod per the spec
        cost_pass = mesh_name == "single"
    t0 = time.time()
    try:
        with mesh, sh.activation_policy(mesh):
            # --- pass 1: SCANNED program = what actually runs on device.
            # This is the required "lower+compile succeeds" proof for BOTH
            # meshes and the memory fit-proof (while-loop buffers are
            # reused, unlike the unrolled accounting program).
            scan_ov = dict(overrides or {})
            scan_ov["scan_layers"] = True
            fn_s, args_s = build_lowerable(arch, shape, mesh,
                                           overrides=scan_ov,
                                           serving_layout=serving_layout)
            compiled_s = fn_s.lower(*args_s).compile()
            t_scan = time.time()
            rec["compile_scan_s"] = round(t_scan - t0, 2)
            try:
                ma = compiled_s.memory_analysis()
                rec["memory"] = {
                    k: int(getattr(ma, k))
                    for k in ("argument_size_in_bytes",
                              "output_size_in_bytes",
                              "temp_size_in_bytes",
                              "generated_code_size_in_bytes")
                    if hasattr(ma, k)}
                args_b = rec["memory"].get("argument_size_in_bytes", 0)
                temp_b = rec["memory"].get("temp_size_in_bytes", 0)
                rec["memory"]["total_per_device"] = args_b + temp_b
            except Exception as e:  # CPU backend may lack some fields
                rec["memory"] = {"error": str(e)}
            del compiled_s, fn_s, args_s
            rec["status"] = "ok"
            # --- pass 2 (single-pod): UNROLLED cost-accounting program
            # (HLO cost analysis counts while bodies once; unrolling —
            # or affine layer extrapolation for deep nets — restores the
            # true flop/byte/collective totals).
            if cost_pass:
                costs, meta = _cost_pass(arch, shape, mesh,
                                         base_overrides=overrides,
                                         serving_layout=serving_layout)
                rec.update(meta)
                rec["cost"] = {k: v for k, v in costs.items()
                               if not k.startswith("wire/")}
                wires = {k[5:]: v for k, v in costs.items()
                         if k.startswith("wire/")}
                roof = rl.derive_from_parts(
                    arch, shape, mesh_name, mesh.devices.size,
                    costs["flops"], costs["bytes accessed"],
                    wires, get_config(arch))
                rec["roofline"] = roof.as_dict()
                rec["collectives"] = wires
    except Exception as e:
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 2)
    if verbose:
        msg = (f"[{rec['status']:4s}] {arch:26s} {shape_name:12s} "
               f"{mesh_name:6s} {rec['total_s']:7.1f}s")
        if rec["status"] == "ok" and "roofline" in rec:
            r = rec["roofline"]
            msg += (f" | dom={r['dominant']:10s} comp={r['compute_s']:.3e} "
                    f"mem={r['memory_s']:.3e} coll={r['collective_s']:.3e}")
        elif rec["status"] == "ok":
            mem = rec.get("memory", {}).get("total_per_device", 0)
            msg += f" | compiles; mem={mem/1e9:.1f}GB/dev"
        else:
            msg += f" | {rec['error'][:120]}"
        print(msg, flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true",
                    help="run every applicable cell on both meshes")
    ap.add_argument("--meshes", default="single,multi")
    ap.add_argument("--include-paper-model", action="store_true")
    ap.add_argument("--out", default=None, help="JSONL output (appended)")
    ap.add_argument("--resume", action="store_true",
                    help="skip cells already present in --out")
    args = ap.parse_args()

    done = set()
    if args.out and args.resume and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if r.get("status") == "ok":
                        done.add((r["arch"], r["shape"], r["mesh"]))
                except json.JSONDecodeError:
                    pass

    def emit(rec):
        if args.out:
            slim = {k: v for k, v in rec.items() if k != "traceback"}
            with open(args.out, "a") as f:
                f.write(json.dumps(slim) + "\n")

    if args.all:
        archs = ALL_ARCHS if args.include_paper_model else ASSIGNED_ARCHS
        meshes = args.meshes.split(",")
        cells = [(a, s.name, m) for a in archs for s in SHAPES.values()
                 if shape_applicable(get_config(a), s) for m in meshes]
        print(f"dry-run: {len(cells)} cells ({len(done)} already done)")
        n_fail = 0
        for arch, shape_name, mesh_name in cells:
            if (arch, shape_name, mesh_name) in done:
                continue
            rec = run_cell(arch, shape_name, mesh_name)
            emit(rec)
            n_fail += rec["status"] != "ok"
        print(f"dry-run complete; failures: {n_fail}")
        raise SystemExit(1 if n_fail else 0)

    rec = run_cell(args.arch, args.shape, args.mesh)
    emit(rec)
    if rec["status"] == "ok":
        print(json.dumps({k: rec[k] for k in ("memory", "cost", "roofline")},
                         indent=2))
    raise SystemExit(0 if rec["status"] == "ok" else 1)


if __name__ == "__main__":
    main()
