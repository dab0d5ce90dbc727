"""GQA attention: projections, rotary application, three core implementations.

Implementations (``cfg.attn_impl`` + sequence-length heuristics):

* ``direct``   — one einsum chain; used for short sequences.
* ``chunked``  — online-softmax scan over KV chunks (memory-efficient XLA
                 path). This is what the dry-run compiles: peak score memory
                 is (B, H, Sq, chunk) instead of (B, H, Sq, Sk), which is the
                 difference between 3.3 PB and ~100 GB at 32k×32 for
                 granite-34b. FLOPs are identical to direct attention.
* ``flash``    — Pallas TPU kernel (kernels/flash_attention.py); engaged on
                 real TPU backends. Not compilable on the CPU host backend,
                 so the dry-run keeps the chunked path (see DESIGN.md §5).

GQA is computed natively with grouped einsums — KV heads are never
materially repeated.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.common import Params, dense_init, pdtype, split_keys


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, d_in: Optional[int] = None) -> Params:
    d = d_in or cfg.d_model
    hq = cfg.num_heads * cfg.head_dim
    hkv = cfg.num_kv_heads * cfg.head_dim
    ks = split_keys(key, ["wq", "wk", "wv", "wo"])
    return {
        "wq": dense_init(ks["wq"], (d, hq), dtype=pdtype(cfg)),
        "wk": dense_init(ks["wk"], (d, hkv), dtype=pdtype(cfg)),
        "wv": dense_init(ks["wv"], (d, hkv), dtype=pdtype(cfg)),
        "wo": dense_init(ks["wo"], (hq, cfg.d_model), dtype=pdtype(cfg)),
    }


#: the weights ``qkv_proj`` and ``out_proj`` consume at the activation dtype
MATMUL_WEIGHTS = ("wq", "wk", "wv", "wo")


def qkv_proj(cfg: ModelConfig, p: Params, x):
    """x (B, S, d) -> q (B,S,Hq,D), k,v (B,S,Hkv,D)."""
    B, S, _ = x.shape
    dt = x.dtype
    q = (x @ p["wq"].astype(dt)).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = (x @ p["wk"].astype(dt)).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = (x @ p["wv"].astype(dt)).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "heads", None)
    v = constrain(v, "batch", "seq", "heads", None)
    return q, k, v


def out_proj(cfg: ModelConfig, p: Params, o):
    B, S = o.shape[:2]
    o = constrain(o, "batch", "seq", "heads", None)
    out = o.reshape(B, S, cfg.num_heads * cfg.head_dim) @ p["wo"].astype(o.dtype)
    return constrain(out, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# Core attention
# ---------------------------------------------------------------------------


def _repeat_kv(k, num_q_heads):
    """(B,S,Hkv,D) -> (B,S,Hq,D).

    GQA KV heads are repeated to the full query-head count on the XLA path
    so the head dimension stays shardable under tensor parallelism (scores
    with Hkv < TP-degree would otherwise replicate — the 154 GB/device
    failure mode). The Pallas kernels resolve GQA in their index maps and
    never materialise this. Cost: Hq/Hkv× KV activation bytes, which is
    orders of magnitude below the score tensors it lets GSPMD shard.
    """
    B, S, Hkv, D = k.shape
    G = num_q_heads // Hkv
    if G == 1:
        return k
    return constrain(jnp.repeat(k, G, axis=2), "batch", "seq", "heads", None)


def _mask_bias(mask):
    return jnp.where(mask, 0.0, -1e30).astype(jnp.float32)


def attention_direct(q, k, v, *, causal: bool, q_offset: int = 0,
                     kv_len=None, window: int = 0, seq_shard: bool = False):
    """q (B,Sq,Hq,D); k,v (B,Sk,Hkv,D) -> (B,Sq,Hq,D).

    ``kv_len`` (scalar or (B,)) masks out cache positions >= kv_len.
    ``window`` > 0 restricts attention to the trailing window.
    ``seq_shard``: sequence-parallel decode (flash-decoding layout): q is
    tiny, so replicate its heads and keep the SCORES sharded along the
    cache's sequence dimension — otherwise GSPMD all-gathers the whole
    seq-sharded KV cache to produce head-sharded scores (23.6 GB/step on
    granite decode_32k). Softmax partials + the pv psum are then the
    standard log-sum-exp combine, inserted by GSPMD.
    """
    if seq_shard:
        return _attention_decode_sp(q, k, v, q_offset=q_offset,
                                    kv_len=kv_len, window=window)
    B, Sq, Hq, D = q.shape
    k = _repeat_kv(k, Hq)
    v = _repeat_kv(v, Hq)
    scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(k.shape[1])
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window:
        mask &= k_pos[None, :] > (q_pos[:, None] - window)
    bias = _mask_bias(mask)[None, None]
    if kv_len is not None:
        kv_len = jnp.asarray(kv_len)
        live = k_pos[None, :] < kv_len.reshape(-1, 1)          # (B or 1, Sk)
        bias = bias + _mask_bias(live)[:, None, None, :]
    probs = jax.nn.softmax(scores + bias, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return out


def _attention_decode_sp(q, k, v, *, q_offset=0, kv_len=None,
                         window: int = 0):
    """Sequence-parallel decode attention (flash-decoding layout).

    q (B,Sq,Hq,D) is tiny → replicated across 'model'; the KV cache stays
    SEQUENCE-sharded and is NEVER repeated/gathered: the grouped einsum
    keeps Hkv intact, scores are sharded along the cache sequence, and
    GSPMD inserts the log-sum-exp combine (softmax partials + pv psum).
    """
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    q = constrain(q, "batch", None, None, None)
    k = constrain(k, "batch", "seq_model", None, None)
    v = constrain(v, "batch", "seq_model", None, None)
    qg = q.reshape(B, Sq, Hkv, G, D)
    scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    scores = constrain(scores, "batch", None, None, None, "seq_model")
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if window:
        mask &= k_pos[None, :] > (q_pos[:, None] - window)
    bias = _mask_bias(mask)[None, None, None]
    if kv_len is not None:
        kv_len = jnp.asarray(kv_len)
        live = k_pos[None, :] < kv_len.reshape(-1, 1)
        bias = bias + _mask_bias(live)[:, None, None, None, :]
    probs = jax.nn.softmax(scores + bias, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(B, Sq, Hq, D)


def attention_chunked(q, k, v, *, causal: bool, chunk: int = 1024,
                      window: int = 0, unroll: bool = False,
                      chunk_remat: bool = False):
    """Online-softmax attention scanning over KV chunks (flash-style in XLA).

    Peak memory is (B, Hq, Sq, chunk) scores per step. ``unroll=True``
    replaces the scan with a python loop — used by the dry-run so HLO cost
    analysis sees the true flop/byte totals (while bodies are counted once).
    """
    B, Sq, Hq, D = q.shape
    Sk = k.shape[1]
    if Sk % chunk != 0:  # fall back for ragged sizes
        return attention_direct(q, k, v, causal=causal, window=window)
    k = _repeat_kv(k, Hq)
    v = _repeat_kv(v, Hq)
    n = Sk // chunk
    scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)
    ks = k.reshape(B, n, chunk, Hq, D).swapaxes(0, 1)    # (n,B,c,Hq,D)
    vs = v.reshape(B, n, chunk, Hq, D).swapaxes(0, 1)
    q_pos = jnp.arange(Sq)

    def body(carry, inp):
        m, l, o = carry
        kc, vc, idx = inp
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kc,
                            preferred_element_type=jnp.float32) * scale
        k_pos = idx * chunk + jnp.arange(chunk)
        mask = jnp.ones((Sq, chunk), bool)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window:
            mask &= k_pos[None, :] > (q_pos[:, None] - window)
        scores = scores + _mask_bias(mask)[None, None]
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(q.dtype), vc).astype(jnp.float32)
        return (m_new, l_new, o_new), None

    body_fn = jax.checkpoint(body) if chunk_remat else body
    m0 = jnp.full((B, Hq, Sq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Hq, Sq), jnp.float32)
    o0 = jnp.zeros((B, Hq, Sq, D), jnp.float32)
    if unroll:
        carry = (m0, l0, o0)
        for i in range(n):
            carry, _ = body_fn(carry, (ks[i], vs[i], i))
        m, l, o = carry
    else:
        (m, l, o), _ = jax.lax.scan(body_fn, (m0, l0, o0),
                                    (ks, vs, jnp.arange(n)))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def attention_flash(q, k, v, *, causal: bool, interpret: bool = False):
    """Pallas TPU flash-attention kernel (see kernels/flash_attention.py)."""
    from repro.kernels import ops  # lazy: kernels are an optional hot path
    return ops.flash_attention(q, k, v, causal=causal, interpret=interpret)


def attend(cfg: ModelConfig, q, k, v, *, causal: bool = True,
           q_offset: int = 0, kv_len=None, window: int = 0):
    """Dispatch on cfg.attn_impl and sequence length."""
    Sk = k.shape[1]
    if cfg.attn_impl == "flash" and kv_len is None:
        return attention_flash(q, k, v, causal=causal)
    if Sk > cfg.attn_chunk_threshold and kv_len is None and q_offset == 0:
        # cap the chunk count so the unrolled (dry-run) path stays compact
        chunk = max(cfg.attn_chunk_size, Sk // 8)
        return attention_chunked(q, k, v, causal=causal, chunk=chunk,
                                 window=window, unroll=not cfg.scan_layers,
                                 chunk_remat=cfg.attn_chunk_remat)
    return attention_direct(q, k, v, causal=causal, q_offset=q_offset,
                            kv_len=kv_len, window=window)


# ---------------------------------------------------------------------------
# KV-cache decode step
# ---------------------------------------------------------------------------


def decode_attend(cfg: ModelConfig, q, cache_k, cache_v, index,
                  window: int = 0):
    """One-token decode: q (B,1,Hq,D) against cache (B,Smax,Hkv,D).

    ``index`` — number of valid positions already in the cache *including*
    the newly-written token (scalar int32).
    """
    q_offset = (index - 1) if window else 0
    return attention_direct(q, cache_k, cache_v, causal=False,
                            kv_len=index, window=window, q_offset=q_offset,
                            seq_shard=cfg.decode_seq_shard)


def cache_update(cache_k, cache_v, k_new, v_new, index, masked: bool = False):
    """Write (B,1,Hkv,D) new KV at position ``index`` of (B,Smax,Hkv,D).

    ``masked=True`` replaces the dynamic_update_slice with a shard-local
    masked write: under a SEQUENCE-sharded cache, GSPMD compiles the dynamic
    slice-write at a traced index into an all-gather + update + reshard of
    the whole cache (23.6 GB/step on granite decode_32k), whereas the
    elementwise where() stays local (every shard tests its own positions) at
    the cost of touching the cache once more in HBM (~2 ms vs ~470 ms ICI).
    Keep the slice write for head/batch-sharded caches where it is free.
    """
    if masked:
        S = cache_k.shape[1]
        pos = (jax.lax.iota(jnp.int32, S) == index)[None, :, None, None]
        ck = jnp.where(pos, k_new.astype(cache_k.dtype), cache_k)
        cv = jnp.where(pos, v_new.astype(cache_v.dtype), cache_v)
        return ck, cv
    ck = jax.lax.dynamic_update_slice(cache_k, k_new.astype(cache_k.dtype),
                                      (0, index, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache_v, v_new.astype(cache_v.dtype),
                                      (0, index, 0, 0))
    return ck, cv
