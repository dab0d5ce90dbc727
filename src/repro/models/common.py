"""Shared model building blocks: norms, embeddings, init helpers.

All models are functional: parameters are nested dicts of ``jnp`` arrays,
forward passes are pure functions of ``(params, inputs, cfg)``. Per-layer
parameters are stacked along a leading layer axis so the layer stack can be
driven by ``jax.lax.scan`` (compact HLO — essential for 512-way GSPMD
compiles on this container's single CPU core).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

Params = Dict[str, Any]


def adtype(cfg: ModelConfig):
    return jnp.dtype(cfg.activation_dtype)


def pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# Initialisation
# ---------------------------------------------------------------------------


def dense_init(key, shape, scale: float = 0.02, dtype=jnp.float32):
    """Truncated-normal-ish init (normal is fine at these scales)."""
    return (scale * jax.random.normal(key, shape)).astype(dtype)


def split_keys(key, names):
    keys = jax.random.split(key, len(names))
    return dict(zip(names, keys))


# ---------------------------------------------------------------------------
# Normalisation
# ---------------------------------------------------------------------------


def rmsnorm(x, weight, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dt)


def layernorm(x, weight, bias, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    out = x * weight.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(dt)


def init_norm(cfg: ModelConfig, d: int | None = None) -> Params:
    d = d or cfg.d_model
    if cfg.norm_type == "rmsnorm":
        return {"weight": jnp.ones((d,), pdtype(cfg))}
    return {"weight": jnp.ones((d,), pdtype(cfg)),
            "bias": jnp.zeros((d,), pdtype(cfg))}


def apply_norm(cfg: ModelConfig, p: Params, x):
    if cfg.norm_type == "rmsnorm":
        return rmsnorm(x, p["weight"], cfg.norm_eps)
    return layernorm(x, p["weight"], p.get("bias"), cfg.norm_eps)


# ---------------------------------------------------------------------------
# Embeddings / logits
# ---------------------------------------------------------------------------


def init_embeddings(key, cfg: ModelConfig) -> Params:
    ks = split_keys(key, ["tok", "pos", "head"])
    p: Params = {"tok": dense_init(ks["tok"], (cfg.vocab_size, cfg.d_model),
                                   dtype=pdtype(cfg))}
    if cfg.pos_type == "learned":
        p["pos"] = dense_init(ks["pos"], (cfg.max_position, cfg.d_model),
                              dtype=pdtype(cfg))
    if not cfg.tie_embeddings:
        p["head"] = dense_init(ks["head"], (cfg.vocab_size, cfg.d_model),
                               dtype=pdtype(cfg))
    return p


#: the tables ``embed_tokens`` and ``logits_head`` consume at the
#: activation dtype (a take then a cast equals a cast then a take)
EMBEDDING_TABLES = ("tok", "pos", "head")


def embed_tokens(cfg: ModelConfig, p: Params, tokens, positions=None):
    """tokens (B, S) int32 -> (B, S, d) activations."""
    from repro.distributed.sharding import constrain
    x = jnp.take(p["tok"], tokens, axis=0).astype(adtype(cfg))
    if cfg.pos_type == "learned":
        if positions is None:
            positions = jnp.arange(tokens.shape[-1])[None, :]
        x = x + jnp.take(p["pos"], positions, axis=0).astype(adtype(cfg))
    return constrain(x, "batch", "seq", "embed")


def logits_head(cfg: ModelConfig, p: Params, x):
    """x (..., d) -> (..., V) logits in ``cfg.logits_dtype``."""
    from repro.distributed.sharding import constrain
    w = p["tok"] if cfg.tie_embeddings else p["head"]
    out = jnp.einsum("...d,vd->...v", x, w.astype(x.dtype))
    if out.ndim == 3:
        out = constrain(out, "batch", "seq", "vocab")
    return out.astype(jnp.dtype(cfg.logits_dtype))


def cross_entropy_loss(logits, labels, mask=None):
    """Token-level CE; logits (..., V) any float dtype, labels (...) int."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def chunked_cross_entropy(cfg: ModelConfig, emb_params: Params, x, labels,
                          chunk: int = 512, mask=None):
    """CE over sequence chunks without materialising (B, S, V) logits.

    Beyond-paper memory optimisation for huge-vocab archs (qwen*-152k):
    scans over S in chunks, computing per-chunk logits + logsumexp only.
    """
    B, S, D = x.shape
    n = S // chunk
    assert n * chunk == S, f"seq {S} not divisible by ce chunk {chunk}"
    xs = x.reshape(B, n, chunk, D).swapaxes(0, 1)          # (n, B, c, D)
    ys = labels.reshape(B, n, chunk).swapaxes(0, 1)        # (n, B, c)
    if mask is None:
        ms = jnp.ones((n, B, chunk), jnp.float32)
    else:
        ms = mask.reshape(B, n, chunk).swapaxes(0, 1).astype(jnp.float32)

    def body(carry, inp):
        tot, cnt = carry
        xc, yc, mc = inp
        logits = logits_head(cfg, emb_params, xc).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        tot = tot + jnp.sum((lse - gold) * mc)
        cnt = cnt + jnp.sum(mc)
        return (tot, cnt), None

    # checkpoint per chunk: without it, grad-of-scan stashes every chunk's
    # logits in residuals and the memory win evaporates
    body_fn = jax.checkpoint(body)
    carry = (jnp.float32(0), jnp.float32(0))
    if cfg.scan_layers:
        (tot, cnt), _ = jax.lax.scan(body_fn, carry, (xs, ys, ms))
    else:  # unrolled for dry-run cost accounting (see scan_or_unroll)
        for i in range(n):
            carry, _ = body_fn(carry, (xs[i], ys[i], ms[i]))
        tot, cnt = carry
    return tot / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# Misc
# ---------------------------------------------------------------------------


def causal_mask(sq: int, sk: int, q_offset: int = 0):
    """Boolean (sq, sk) mask: True = attend."""
    q_pos = q_offset + jnp.arange(sq)[:, None]
    k_pos = jnp.arange(sk)[None, :]
    return k_pos <= q_pos


def count_params(params) -> int:
    return int(sum(np.prod(x.shape) for x in jax.tree_util.tree_leaves(params)))


def scan_or_unroll(body, carry, stacked, scan: bool, length: int | None = None):
    """``lax.scan`` over leading-axis-stacked params, or a python unroll.

    The unrolled path exists for the dry-run roofline: XLA's HLO cost
    analysis counts a while-loop body ONCE, so flops/bytes/collectives of a
    scanned layer stack would be under-reported by ~num_layers×. Unrolling
    makes the compiled HLO carry the true totals. Same (carry, ys) contract
    as lax.scan.
    """
    if scan:
        return jax.lax.scan(body, carry, stacked)
    if length is None:
        length = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    ys_list = []
    for i in range(length):
        sl = jax.tree.map(lambda a: a[i], stacked)
        carry, y = body(carry, sl)
        ys_list.append(y)
    if ys_list and ys_list[0] is not None:
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys_list)
    else:
        ys = None
    return carry, ys
