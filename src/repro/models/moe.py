"""Mixture-of-Experts layer with sorted-scatter capacity dispatch.

TPU-friendly "dropping" dispatch (the MaxText/Switch lineage), expressed so
GSPMD can shard it: tokens live on the ``data`` axis, expert weight stacks on
the ``model`` axis, and the scatter/gather pair between the two becomes the
expert-parallel all-to-all.

Algorithm per layer:
  1. router logits -> top-k experts + renormalised gates (float32)
  2. flatten (token, k) assignments; stable-sort by expert id
  3. rank-within-expert via cumulative counts; drop rank >= capacity
  4. scatter tokens into an (E, capacity, d) buffer, batched expert FFN,
     gather back, gate-weighted combine.

The (T, E, capacity) one-hot dispatch einsum used by small-scale MoE
implementations is deliberately avoided: at prefill_32k on qwen3-moe it would
materialise a ~10^13-element tensor.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.common import Params, dense_init, pdtype, split_keys


def moe_capacity(cfg: ModelConfig, num_tokens: int) -> int:
    cap = math.ceil(num_tokens * cfg.experts_per_token / cfg.num_experts
                    * cfg.moe_capacity_factor)
    return max(8, int(math.ceil(cap / 8) * 8))


def init_moe(key, cfg: ModelConfig) -> Params:
    E, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    ks = split_keys(key, ["router", "wi", "wg", "wo"])
    p = {
        "router": dense_init(ks["router"], (d, E), dtype=pdtype(cfg)),
        "wi": dense_init(ks["wi"], (E, d, f), dtype=pdtype(cfg)),
        "wo": dense_init(ks["wo"], (E, f, d), dtype=pdtype(cfg)),
    }
    if cfg.act == "silu":
        p["wg"] = dense_init(ks["wg"], (E, d, f), dtype=pdtype(cfg))
    return p


#: the expert stacks ``apply_moe`` consumes at the activation dtype; the
#: router is consumed at float32 (``route_topk``) and is not among them
MATMUL_WEIGHTS = ("wi", "wg", "wo")


def route_topk(cfg: ModelConfig, p: Params, xf):
    """xf (T, d) -> gates (T, k) f32, idx (T, k) i32, router probs (T, E)."""
    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return gates, idx, probs


def load_balance_loss(cfg: ModelConfig, probs, idx):
    """Switch-style auxiliary loss: E * sum_e f_e * P_e."""
    E = cfg.num_experts
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)      # (T, k, E)
    frac_tokens = jnp.mean(jnp.sum(onehot, axis=1), axis=0)  # (E,)
    frac_probs = jnp.mean(probs, axis=0)
    return E * jnp.sum(frac_tokens * frac_probs) / cfg.experts_per_token


def apply_moe(cfg: ModelConfig, p: Params, x, return_aux: bool = False):
    """x (B, S, d) -> (B, S, d) [, aux_loss]."""
    B, S, d = x.shape
    T = B * S
    k = cfg.experts_per_token
    E = cfg.num_experts
    C = moe_capacity(cfg, T)
    dt = x.dtype
    xf = x.reshape(T, d)

    gates, idx, probs = route_topk(cfg, p, xf)

    flat_expert = idx.reshape(T * k)                       # row-major: t*k + j
    flat_gate = gates.reshape(T * k)
    flat_token = jnp.arange(T * k, dtype=jnp.int32) // k

    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    sorted_token = flat_token[order]
    sorted_gate = flat_gate[order]

    counts = jnp.zeros((E,), jnp.int32).at[flat_expert].add(1)
    starts = jnp.cumsum(counts) - counts                   # (E,)
    rank = jnp.arange(T * k, dtype=jnp.int32) - starts[sorted_expert]
    keep = rank < C
    rank_c = jnp.where(keep, rank, 0)

    gathered = jnp.take(xf, sorted_token, axis=0)          # (T*k, d)
    gathered = constrain(gathered * keep[:, None].astype(dt), "batch", None)
    buf = jnp.zeros((E, C, d), dt).at[sorted_expert, rank_c].add(gathered)
    buf = constrain(buf, "expert", None, None)             # EP: a2a here

    h = constrain(jnp.einsum("ecd,edf->ecf", buf, p["wi"].astype(dt)),
                  "expert", None, None)
    if cfg.act == "silu":
        h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", buf,
                                        p["wg"].astype(dt))
    else:
        h = jax.nn.gelu(h, approximate=True)
    out = constrain(jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(dt)),
                    "expert", None, None)

    y_sorted = constrain(out[sorted_expert, rank_c], "batch", None)  # (T*k,d)
    w = (sorted_gate * keep).astype(dt)[:, None]
    y = jnp.zeros((T, d), dt).at[sorted_token].add(y_sorted * w)
    y = constrain(y.reshape(B, S, d), "batch", "seq", "embed")
    if return_aux:
        return y, load_balance_loss(cfg, probs, idx)
    return y
