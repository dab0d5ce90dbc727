"""Decoder-only transformer LM (dense / MoE / VLM backbones).

Layer stack is driven by ``jax.lax.scan`` over leading-axis-stacked
parameters (compact HLO for 512-way GSPMD compiles), with optional
``jax.checkpoint`` rematerialisation per layer.

Families served here: ``dense`` (starcoder2, tinyllama, granite, smollm,
gpt2-large), ``moe`` (phi3.5-moe, qwen3-moe), ``vlm`` (qwen2-vl — stub patch
embeddings + M-RoPE). Whisper / RWKV6 / Zamba2 live in their own modules.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn, mlp, moe as moe_mod
from repro.models.common import (
    EMBEDDING_TABLES,
    Params,
    adtype,
    apply_norm,
    chunked_cross_entropy,
    cross_entropy_loss,
    embed_tokens,
    init_embeddings,
    init_norm,
    logits_head,
    pdtype,
    scan_or_unroll,
    split_keys,
)
from repro.models.mlp import apply_mlp, init_mlp
from repro.models.rope import apply_rotary, positional_angles


# ---------------------------------------------------------------------------
# Per-layer block
# ---------------------------------------------------------------------------


def init_block(key, cfg: ModelConfig) -> Params:
    ks = split_keys(key, ["attn", "ffn", "norm1", "norm2"])
    p = {
        "attn": attn.init_attention(ks["attn"], cfg),
        "norm1": init_norm(cfg),
        "norm2": init_norm(cfg),
    }
    if cfg.family == "moe":
        p["ffn"] = moe_mod.init_moe(ks["ffn"], cfg)
    else:
        p["ffn"] = init_mlp(ks["ffn"], cfg)
    return p


def _ffn(cfg: ModelConfig, p: Params, x):
    if cfg.family == "moe":
        return moe_mod.apply_moe(cfg, p, x, return_aux=True)
    return apply_mlp(cfg, p, x), jnp.float32(0.0)


def block_forward(cfg: ModelConfig, p: Params, x, angles):
    """Full-sequence (train/prefill) block. Returns (x, (k, v, aux))."""
    h = apply_norm(cfg, p["norm1"], x)
    q, k, v = attn.qkv_proj(cfg, p["attn"], h)
    if angles is not None:
        q = apply_rotary(q, angles)
        k = apply_rotary(k, angles)
    o = attn.attend(cfg, q, k, v, causal=True, window=cfg.sliding_window)
    x = x + attn.out_proj(cfg, p["attn"], o)
    h = apply_norm(cfg, p["norm2"], x)
    y, aux = _ffn(cfg, p["ffn"], h)
    return x + y, (k, v, aux)


def block_decode(cfg: ModelConfig, p: Params, x, angles, cache_k, cache_v,
                 index):
    """One-token block. x (B,1,d); caches (B,Smax,Hkv,D). Returns
    (x, cache_k, cache_v)."""
    h = apply_norm(cfg, p["norm1"], x)
    q, k, v = attn.qkv_proj(cfg, p["attn"], h)
    if angles is not None:
        q = apply_rotary(q, angles)
        k = apply_rotary(k, angles)
    cache_k, cache_v = attn.cache_update(cache_k, cache_v, k, v, index,
                                         masked=cfg.decode_masked_write)
    o = attn.decode_attend(cfg, q, cache_k, cache_v, index + 1,
                           window=cfg.sliding_window)
    x = x + attn.out_proj(cfg, p["attn"], o)
    h = apply_norm(cfg, p["norm2"], x)
    y, _ = _ffn(cfg, p["ffn"], h)
    return x + y, cache_k, cache_v


# ---------------------------------------------------------------------------
# Model init / forward
# ---------------------------------------------------------------------------


def init(key, cfg: ModelConfig) -> Params:
    kemb, klayers, kf = jax.random.split(key, 3)
    layer_keys = jax.random.split(klayers, cfg.num_layers)
    layers = jax.vmap(lambda k: init_block(k, cfg))(layer_keys)
    return {
        "embed": init_embeddings(kemb, cfg),
        "layers": layers,              # leading axis = layer
        "final_norm": init_norm(cfg),
    }


def activation_dtype_params(cfg: ModelConfig, params: Params) -> Params:
    """``params`` with every weight that the forward consumes only at the
    activation dtype cast to it, and every other leaf as stored.

    Cast: the attention and FFN matmul weights and the embedding tables.
    Their consumers cast them to the activation dtype at each use, so a
    copy cast once gives the same numbers without a conversion per call.
    Kept: norm weights and biases (the norms upcast them to float32) and
    the MoE router (consumed at float32). A leaf's role is its name under
    its parent (``embed``, ``attn``, ``ffn``), so the tree may be the
    whole model or any part that keeps those parents, such as a stage's
    slice of the layer stacks. Returns ``params`` itself when the two
    dtypes agree."""
    dt = adtype(cfg)
    if dt == pdtype(cfg):
        return params
    ffn = moe_mod if cfg.family == "moe" else mlp
    roles = {"embed": EMBEDDING_TABLES, "attn": attn.MATMUL_WEIGHTS,
             "ffn": ffn.MATMUL_WEIGHTS}

    def cast(path, leaf):
        keys = [getattr(k, "key", None) for k in path[-2:]]
        if len(keys) == 2 and keys[1] in roles.get(keys[0], ()):
            return leaf.astype(dt)
        return leaf

    return jax.tree_util.tree_map_with_path(cast, params)


def _angles(cfg: ModelConfig, positions):
    if positions is None:
        return None
    return positional_angles(cfg, positions)


def forward_hidden(cfg: ModelConfig, params: Params, tokens, positions=None,
                   prefix_embeds=None, collect_kv: bool = False):
    """tokens (B,S) -> hidden (B,S,d). Optionally returns stacked KV.

    ``prefix_embeds`` (B, Sv, d): modality-stub embeddings prepended to the
    token embeddings (VLM path). ``positions`` may be (B,S_total) or
    (3,B,S_total) for M-RoPE.
    """
    x = embed_tokens(cfg, params["embed"], tokens,
                     positions if cfg.pos_type == "learned" and positions is not None
                     and positions.ndim == 2 else None)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    if positions is None and cfg.pos_type in ("rope", "mrope"):
        positions = jnp.arange(S)[None, :].repeat(B, 0)
    angles = _angles(cfg, positions) if cfg.pos_type in ("rope", "mrope") else None

    def body(x, lp):
        x, (k, v, aux) = block_forward(cfg, lp, x, angles)
        ys = (k, v, aux) if collect_kv else aux
        return x, ys

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, ys = scan_or_unroll(body_fn, x, params["layers"],
                           scan=cfg.scan_layers, length=cfg.num_layers)
    x = apply_norm(cfg, params["final_norm"], x)
    if collect_kv:
        k, v, aux = ys
        return x, (k, v), jnp.mean(aux)
    return x, None, jnp.mean(ys)


# ---------------------------------------------------------------------------
# Training loss
# ---------------------------------------------------------------------------


def loss_fn(cfg: ModelConfig, params: Params, batch) -> jnp.ndarray:
    """batch: tokens (B,S), labels (B,S) [, mask, positions, vision_embeds]."""
    tokens = batch["tokens"]
    prefix = batch.get("vision_embeds")
    x, _, aux = forward_hidden(cfg, params, tokens,
                               positions=batch.get("positions"),
                               prefix_embeds=prefix)
    if prefix is not None:  # loss only over the text region
        x = x[:, prefix.shape[1]:, :]
    labels = batch["labels"]
    mask = batch.get("mask")
    if cfg.ce_impl == "chunked":
        loss = chunked_cross_entropy(cfg, params["embed"], x, labels,
                                     chunk=cfg.ce_chunk, mask=mask)
    else:
        logits = logits_head(cfg, params["embed"], x)
        loss = cross_entropy_loss(logits, labels, mask)
    if cfg.family == "moe":
        loss = loss + cfg.moe_aux_weight * aux
    return loss


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------


def make_cache(cfg: ModelConfig, batch: int, capacity: int, dtype=None):
    dtype = dtype or adtype(cfg)
    shape = (cfg.num_layers, batch, capacity, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "index": jnp.zeros((), jnp.int32)}


def prefill(cfg: ModelConfig, params: Params, tokens, positions=None,
            prefix_embeds=None, capacity: Optional[int] = None):
    """Process the prompt; returns (last-token logits, cache)."""
    x, (k, v), _ = forward_hidden(cfg, params, tokens, positions=positions,
                                  prefix_embeds=prefix_embeds, collect_kv=True)
    S = k.shape[2]
    capacity = capacity or S
    if capacity > S:
        pad = [(0, 0), (0, 0), (0, capacity - S), (0, 0), (0, 0)]
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    logits = logits_head(cfg, params["embed"], x[:, -1:, :])
    cache = {"k": k, "v": v, "index": jnp.asarray(S, jnp.int32)}
    return logits, cache


def decode_step(cfg: ModelConfig, params: Params, token, cache,
                positions=None):
    """token (B,1) int32; cache from prefill/make_cache. One serve step."""
    index = cache["index"]
    B = token.shape[0]
    x = embed_tokens(cfg, params["embed"], token,
                     positions=jnp.full((B, 1), index)
                     if cfg.pos_type == "learned" else None)
    if cfg.pos_type in ("rope", "mrope"):
        if positions is None:
            positions = jnp.full((B, 1), index, jnp.int32)
        angles = _angles(cfg, positions)
    else:
        angles = None

    def body(x, inp):
        lp, ck, cv = inp
        x, ck, cv = block_decode(cfg, lp, x, angles, ck, cv, index)
        return x, (ck, cv)

    x, (K, V) = scan_or_unroll(body, x,
                               (params["layers"], cache["k"], cache["v"]),
                               scan=cfg.scan_layers, length=cfg.num_layers)
    x = apply_norm(cfg, params["final_norm"], x)
    logits = logits_head(cfg, params["embed"], x)
    new_cache = {"k": K, "v": V, "index": index + 1}
    return logits, new_cache
