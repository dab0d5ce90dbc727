"""Roofline derivation from compiled dry-run artifacts.

Three terms per (arch × shape × mesh) cell, in seconds (TPU v5e constants):

    compute    = HLO_FLOPs_per_device / 197e12        (bf16 MXU peak)
    memory     = HLO_bytes_per_device / 819e9         (HBM bandwidth)
    collective = wire_bytes_per_device / 50e9         (per-link ICI)

``cost_analysis()`` on the compiled (post-SPMD) module reports per-device
flops / bytes. Collective bytes are NOT in cost_analysis, so we parse the
compiled HLO text and apply ring-algorithm wire factors to each op's result
shape: all-reduce 2× (reduce-scatter + all-gather phases), all-gather 1×
result, reduce-scatter 1× (full operand leaves the device once),
all-to-all 1×, collective-permute 1×. These are the standard (n-1)/n ≈ 1
ring approximations, documented in EXPERIMENTS.md.

MODEL_FLOPS uses the kind-appropriate useful-work formula: train 6·N·D,
prefill 2·N·D, decode 2·N·tokens (N = active params for MoE); the ratio
against HLO FLOPs exposes remat/dispatch waste.
"""
from __future__ import annotations

import re
from dataclasses import asdict, dataclass
from typing import Dict

from repro.configs.base import ModelConfig, ShapeConfig

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
}

_COLLECTIVE_FACTORS = {
    "all-reduce": 2.0,
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

# result type of a collective op:  `= bf16[8,128]{1,0} all-gather(` ; also
# tuple-shaped results `= (f32[4], f32[4]) all-reduce(`
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s*(\([^)]*\)|\w+\[[\d,]*\][^ ]*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_wire_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device wire bytes by collective kind, from compiled HLO text."""
    out: Dict[str, float] = {k: 0.0 for k in _COLLECTIVE_FACTORS}
    count = 0
    for m in _OP_RE.finditer(hlo_text):
        type_str, kind, started = m.group(1), m.group(2), m.group(3)
        if started and kind in ("all-reduce", "all-gather"):
            # -start ops: result tuple repeats operand; take half
            b = _shape_bytes(type_str) / 2
        else:
            b = _shape_bytes(type_str)
        out[kind] += b * _COLLECTIVE_FACTORS[kind]
        count += 1
    out["num_ops"] = count
    out["total"] = sum(v for k, v in out.items()
                       if k in _COLLECTIVE_FACTORS)
    return out


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    flops_per_device: float
    bytes_per_device: float
    wire_bytes_per_device: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops_total: float
    hlo_flops_total: float
    useful_ratio: float
    collective_ops: int = 0
    model_flops_ext: float = 0.0   # incl. analytic attention quadratic
    useful_ratio_ext: float = 0.0  # model_flops_ext / HLO_FLOPs

    def as_dict(self):
        return asdict(self)


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    n = cfg.active_param_count()
    toks = shape.global_batch * shape.seq_len
    if shape.kind == "train":
        return 6.0 * n * toks
    if shape.kind == "prefill":
        return 2.0 * n * toks
    return 2.0 * n * shape.global_batch          # decode: one token / seq


def attention_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """Analytic causal-attention FLOPs (qk + pv, lower triangle only) —
    the quadratic term 6·N·D misses, dominant at 32k+. For decode: one
    query row against the full cache."""
    if cfg.family == "ssm":
        return 0.0
    d_attn = cfg.num_heads * cfg.head_dim
    B, S = shape.global_batch, shape.seq_len
    if cfg.family == "hybrid":
        layers = len(cfg.hybrid_layer_ids)
    elif cfg.is_encoder_decoder:
        layers = cfg.enc_layers + 2 * cfg.num_layers  # self + cross
    else:
        layers = cfg.num_layers
    if shape.kind == "decode":
        return 4.0 * B * S * d_attn * layers
    tri = 0.5 if not cfg.is_encoder_decoder else 1.0
    fwd = 4.0 * B * S * S * d_attn * layers * tri
    return 3.0 * fwd if shape.kind == "train" else fwd


def model_flops_ext(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """6·N·D-style useful work INCLUDING the attention quadratic term."""
    return model_flops(cfg, shape) + attention_flops(cfg, shape)


def derive_from_parts(arch: str, shape: ShapeConfig, mesh_name: str,
                      num_devices: int, flops_dev: float, bytes_dev: float,
                      wires: Dict[str, float], cfg: ModelConfig) -> Roofline:
    compute_s = flops_dev / PEAK_FLOPS
    memory_s = bytes_dev / HBM_BW
    collective_s = wires.get("total", 0.0) / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    mflops = model_flops(cfg, shape)
    mext = model_flops_ext(cfg, shape)
    hlo_total = flops_dev * num_devices
    return Roofline(
        arch=arch, shape=shape.name, mesh=mesh_name,
        flops_per_device=flops_dev, bytes_per_device=bytes_dev,
        wire_bytes_per_device=wires.get("total", 0.0),
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=dominant,
        model_flops_total=mflops, hlo_flops_total=hlo_total,
        useful_ratio=(mflops / hlo_total) if hlo_total else 0.0,
        collective_ops=int(wires.get("num_ops", 0)),
        model_flops_ext=mext,
        useful_ratio_ext=(mext / hlo_total) if hlo_total else 0.0,
    )


def derive(arch: str, shape: ShapeConfig, mesh_name: str, num_devices: int,
           cost: Dict, hlo_text: str, cfg: ModelConfig) -> Roofline:
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    wires = collective_wire_bytes(hlo_text)
    compute_s = flops_dev / PEAK_FLOPS
    memory_s = bytes_dev / HBM_BW
    collective_s = wires["total"] / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    mflops = model_flops(cfg, shape)
    mext = model_flops_ext(cfg, shape)
    hlo_total = flops_dev * num_devices
    return Roofline(
        arch=arch, shape=shape.name, mesh=mesh_name,
        flops_per_device=flops_dev, bytes_per_device=bytes_dev,
        wire_bytes_per_device=wires["total"],
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=dominant,
        model_flops_total=mflops, hlo_flops_total=hlo_total,
        useful_ratio=(mflops / hlo_total) if hlo_total else 0.0,
        collective_ops=int(wires["num_ops"]),
        model_flops_ext=mext,
        useful_ratio_ext=(mext / hlo_total) if hlo_total else 0.0,
    )
