"""Unified model API: ``build_model(cfg)`` dispatches families to their
implementation modules and exposes a uniform functional surface:

    model.init(key)                      -> params
    model.loss_fn(params, batch)         -> scalar loss          (train_step)
    model.prefill(params, **inputs)      -> (logits, cache)      (prefill)
    model.decode_step(params, token, cache) -> (logits, cache)   (serve_step)
    model.served_stages(params, segments) -> per-stage weights    (routed
                                            pipeline, one per [start, end))
    model.stage_forward(stage, tokens, x, first=, last=) -> x or logits
    model.stage_bucket                   -> multiple to which stage hops may
                                            right-pad positions (None: never)
    model.input_specs(shape)             -> ShapeDtypeStruct pytrees for the
                                            dry-run (no allocation)

``input_specs`` is the dry-run contract: for every assigned shape it returns
(args, kwargs) stand-ins that are weak-type-correct and shardable.
Modality-stub rule: [audio]/[vlm] specs include precomputed frame/patch
embeddings, never raw pixels/waveforms.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import rwkv6, transformer, whisper, zamba2
from repro.models.common import adtype


@dataclass
class Model:
    cfg: ModelConfig
    init: Callable
    loss_fn: Callable
    prefill: Callable
    decode_step: Callable
    served_stages: Callable
    stage_forward: Callable
    stage_bucket: Optional[int] = None

    # ------------------------------------------------------------------
    def train_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStruct batch for loss_fn."""
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        tok = lambda s: jax.ShapeDtypeStruct(s, i32)
        if cfg.family == "audio":
            return {"frames": jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                                   adtype(cfg)),
                    "tokens": tok((B, S)), "labels": tok((B, S))}
        if cfg.family == "vlm":
            sv = min(1024, S // 4)
            st = S - sv
            return {"tokens": tok((B, st)),
                    "vision_embeds": jax.ShapeDtypeStruct((B, sv, cfg.d_model),
                                                          adtype(cfg)),
                    "positions": tok((3, B, S)),
                    "labels": tok((B, st))}
        return {"tokens": tok((B, S)), "labels": tok((B, S))}

    def prefill_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        tok = lambda s: jax.ShapeDtypeStruct(s, i32)
        if cfg.family == "audio":
            return {"tokens": tok((B, S)),
                    "frames": jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                                   adtype(cfg))}
        if cfg.family == "vlm":
            sv = min(1024, S // 4)
            return {"tokens": tok((B, S - sv)),
                    "prefix_embeds": jax.ShapeDtypeStruct((B, sv, cfg.d_model),
                                                          adtype(cfg)),
                    "positions": tok((3, B, S))}
        return {"tokens": tok((B, S))}

    def decode_specs(self, shape: ShapeConfig):
        """(token, cache) ShapeDtypeStructs: one new token, KV cache at
        capacity seq_len with seq_len-1 valid entries."""
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        token = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        cache = jax.eval_shape(lambda: make_cache(cfg, B, S))
        return token, cache

    def input_specs(self, shape: ShapeConfig):
        if shape.kind == "train":
            return self.train_specs(shape)
        if shape.kind == "prefill":
            return self.prefill_specs(shape)
        return self.decode_specs(shape)


# ---------------------------------------------------------------------------
# Cache constructors (decode dry-run + serving)
# ---------------------------------------------------------------------------


def make_cache(cfg: ModelConfig, batch: int, capacity: int):
    if cfg.family == "ssm":
        st = rwkv6.make_state(cfg, batch)
        st["index"] = jnp.zeros((), jnp.int32)
        return st
    if cfg.family == "hybrid":
        return zamba2.make_cache(cfg, batch, capacity)
    if cfg.family == "audio":
        L = cfg.num_layers
        kv = (L, batch, capacity, cfg.num_kv_heads, cfg.head_dim)
        return {"sk": jnp.zeros(kv, adtype(cfg)),
                "sv": jnp.zeros(kv, adtype(cfg)),
                "ck": jnp.zeros(kv, adtype(cfg)),
                "cv": jnp.zeros(kv, adtype(cfg)),
                "index": jnp.zeros((), jnp.int32)}
    return transformer.make_cache(cfg, batch, capacity)


_FAMILY_MODULES = {
    "dense": transformer,
    "moe": transformer,
    "vlm": transformer,
    "audio": whisper,
    "ssm": rwkv6,
    "hybrid": zamba2,
}


def _not_routable(cfg: ModelConfig):
    def refuse(*_a, **_kw):
        raise NotImplementedError(f"the {cfg.family} family has no "
                                  f"pipeline stages (ROADMAP R1)")
    return refuse


def build_model(cfg: ModelConfig) -> Model:
    mod = _FAMILY_MODULES[cfg.family]
    staged = hasattr(mod, "stage_forward")
    return Model(
        cfg=cfg,
        init=lambda key: mod.init(key, cfg),
        loss_fn=lambda params, batch: mod.loss_fn(cfg, params, batch),
        prefill=lambda params, **kw: mod.prefill(cfg, params, **kw),
        decode_step=lambda params, token, cache: mod.decode_step(
            cfg, params, token, cache),
        served_stages=(lambda params, segments: mod.served_stages(
            cfg, params, segments)) if staged else _not_routable(cfg),
        stage_forward=(lambda stage, tokens, x, **kw: mod.stage_forward(
            cfg, stage, tokens, x, **kw)) if staged else _not_routable(cfg),
        stage_bucket=getattr(mod, "STAGE_BUCKET", None),
    )
