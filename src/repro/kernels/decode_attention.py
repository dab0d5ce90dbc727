"""Single-token GQA decode attention over a KV cache — Pallas TPU kernel.

The decode hot spot is *memory-bound*: one query row per (batch, head)
streams the whole KV cache through VMEM once. Grid = (B, Hq, nk) with the KV
block dimension sequential; online-softmax stats in VMEM scratch. Positions
≥ ``kv_len[b]`` are masked (live-length masking — the cache is a ring of
capacity S with ``kv_len`` valid entries).

Arithmetic intensity ≈ 2 FLOPs/byte (2·S·D MACs over S·D·2·2 cache bytes),
so the roofline is the HBM stream of K and V — the kernel's job is purely
to never re-read the cache and to keep the lane dimension dense.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, blk_k: int, nk: int, scale: float):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0, :].astype(jnp.float32)               # (D,)
    k = k_ref[0, :, 0, :].astype(jnp.float32)            # (blk_k, D)
    v = v_ref[0, :, 0, :].astype(jnp.float32)
    kv_len = len_ref[0]

    s = jax.lax.dot_general(k, q * scale, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (blk_k,)
    k_pos = ik * blk_k + jax.lax.iota(jnp.int32, blk_k)
    s = jnp.where(k_pos < kv_len, s, NEG_INF)

    m_prev = m_scr[0]
    m_new = jnp.maximum(m_prev, jnp.max(s))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                               # (blk_k,)
    l_scr[0] = l_scr[0] * alpha + jnp.sum(p)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p[None, :], v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[0] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0, 0, :] = (acc_scr[0] /
                          jnp.maximum(l_scr[0], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blk_k", "interpret"))
def decode_attention(q, cache_k, cache_v, kv_len, *, blk_k: int = 512,
                     interpret: bool = False):
    """q (B,Hq,D); caches (B,S,Hkv,D); kv_len (B,) i32 -> (B,Hq,D)."""
    B, Hq, D = q.shape
    S, Hkv = cache_k.shape[1], cache_k.shape[2]
    blk_k = min(blk_k, S)
    assert S % blk_k == 0, (S, blk_k)
    assert Hq % Hkv == 0
    G = Hq // Hkv
    nk = S // blk_k
    scale = float(1.0 / np.sqrt(D))
    kernel = functools.partial(_decode_kernel, blk_k=blk_k, nk=nk,
                               scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(B, Hq, nk),
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, ik: (b,)),
            pl.BlockSpec((1, 1, D), lambda b, h, ik: (b, h, 0)),
            pl.BlockSpec((1, blk_k, 1, D), lambda b, h, ik: (b, ik, h // G, 0)),
            pl.BlockSpec((1, blk_k, 1, D), lambda b, h, ik: (b, ik, h // G, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, D), lambda b, h, ik: (b, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), q, cache_k, cache_v)
