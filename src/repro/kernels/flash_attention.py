"""Blocked causal GQA flash attention — Pallas TPU kernel.

The prefill/train compute hot spot. Online-softmax over KV blocks with the
running (m, l, acc) statistics held in VMEM scratch that persists across the
sequential ``ik`` grid dimension (TPU grid dims execute in order; the last
dim is marked "arbitrary" so the compiler must not parallelise it).

Tiling: q blocks (blk_q, D) × kv blocks (blk_k, D) per (batch, q-head); the
KV head for query head ``h`` is ``h // (Hq // Hkv)`` — GQA is resolved in
the BlockSpec index maps, never by materialising repeated KV.

VMEM budget per program (defaults blk_q = blk_k = 128, D = 128, f32 compute):
q 64 KiB + k/v 128 KiB + p 64 KiB + acc 64 KiB ≈ 0.4 MiB — far under the
~16 MiB/core budget; blk sizes are MXU-aligned (128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  blk_q: int, blk_k: int, nk: int, scale: float,
                  causal: bool):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, :, 0, :].astype(jnp.float32)            # (blk_q, D)
    k = k_ref[0, :, 0, :].astype(jnp.float32)            # (blk_k, D)
    v = v_ref[0, :, 0, :].astype(jnp.float32)

    s = jax.lax.dot_general(q * scale, k,
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if causal:
        q_pos = iq * blk_q + jax.lax.broadcasted_iota(jnp.int32,
                                                      (blk_q, blk_k), 0)
        k_pos = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32,
                                                      (blk_q, blk_k), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0, :, 0, :] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "blk_q", "blk_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, blk_q: int = 128,
                    blk_k: int = 128, interpret: bool = False):
    """q (B,S,Hq,D); k,v (B,S,Hkv,D) -> (B,S,Hq,D)."""
    B, S, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    blk_q = min(blk_q, S)
    blk_k = min(blk_k, Sk)
    assert S % blk_q == 0 and Sk % blk_k == 0, (S, Sk, blk_q, blk_k)
    assert Hq % Hkv == 0
    G = Hq // Hkv
    nq, nk = S // blk_q, Sk // blk_k
    scale = float(1.0 / np.sqrt(D))

    kernel = functools.partial(_flash_kernel, blk_q=blk_q, blk_k=blk_k,
                               nk=nk, scale=scale, causal=causal)
    return pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, blk_q, 1, D), lambda b, h, iq, ik: (b, iq, h, 0)),
            pl.BlockSpec((1, blk_k, 1, D),
                         lambda b, h, iq, ik: (b, ik, h // G, 0)),
            pl.BlockSpec((1, blk_k, 1, D),
                         lambda b, h, iq, ik: (b, ik, h // G, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, 1, D),
                               lambda b, h, iq, ik: (b, iq, h, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
