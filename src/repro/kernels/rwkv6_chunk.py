"""Chunked WKV6 (RWKV6 time-mix) scan — Pallas TPU kernel.

One grid step processes one (batch, head, chunk) tile; the (K, V) recurrent
state lives in VMEM scratch and persists across the sequential chunk
dimension. Math is identical to ``models.rwkv6.wkv6_chunked`` (and the
token-recurrence oracle in ref.py): all decay ratios are ``exp(non-positive
log-cumsum differences)`` so the kernel is overflow-safe at any decay
strength, and the three contributions per chunk are

    inter : y += (r ⊙ exp(cum_prev)) @ state
    intra : y += (A ⊙ causal) @ v,  A[t,s] = Σ_k r_t k_s exp(cumprev_t−cum_s)
    bonus : y += (Σ_k r_t u k_t) v_t

with the state advanced by ``exp(cum_C)⊙state + (k ⊙ exp(cum_C−cum))ᵀ v``.

VMEM per program (C = chunk, K = head dim; C=64, K=64 f32): the (C, C, K)
exponent-difference tensor dominates at 1 MiB — the chunk size is chosen so
that this tile and the (K, K) state fit comfortably.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, y_ref,
                 sout_ref, state_scr, *, nc: int, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    rc = r_ref[0, :, 0, :].astype(jnp.float32)      # (C, K)
    kc = k_ref[0, :, 0, :].astype(jnp.float32)
    vc = v_ref[0, :, 0, :].astype(jnp.float32)
    lwc = lw_ref[0, :, 0, :].astype(jnp.float32)
    u = u_ref[0, :].astype(jnp.float32)             # (K,)
    state = state_scr[...]                          # (K, V)

    cum = jnp.cumsum(lwc, axis=0)                   # inclusive
    cum_prev = cum - lwc

    # inter-chunk
    r_dec = rc * jnp.exp(cum_prev)
    y = jax.lax.dot_general(r_dec, state, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # intra-chunk (strict lower triangle)
    diff = cum_prev[:, None, :] - cum[None, :, :]   # (C, C, K)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = (s_idx < t_idx)[:, :, None]
    prod = rc[:, None, :] * kc[None, :, :] * jnp.exp(diff)
    A = jnp.sum(jnp.where(tri, prod, 0.0), axis=2)  # (C, C)
    y = y + jax.lax.dot_general(A, vc, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    # bonus (current token)
    Ad = jnp.sum(rc * u[None, :] * kc, axis=1)      # (C,)
    y = y + Ad[:, None] * vc
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    # state carry
    k_dec = kc * jnp.exp(cum[-1:, :] - cum)
    state_new = jnp.exp(cum[-1, :])[:, None] * state + jax.lax.dot_general(
        k_dec, vc, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    state_scr[...] = state_new

    @pl.when(ic == nc - 1)
    def _finalize():
        sout_ref[0, 0] = state_new.astype(sout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_chunked(r, k, v, lw, u, state0, *, chunk: int = 64,
                 interpret: bool = False):
    """r,k,v,lw (B,S,H,K) f32; u (H,K); state0 (B,H,K,K).

    Returns (y (B,S,H,K) f32, final state (B,H,K,K))."""
    B, S, H, K = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    kernel = functools.partial(_wkv6_kernel, nc=nc, chunk=chunk)
    seq_spec = pl.BlockSpec((1, chunk, 1, K), lambda b, h, ic: (b, ic, h, 0))
    y, sout = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((1, K), lambda b, h, ic: (h, 0)),
            pl.BlockSpec((1, 1, K, K), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_specs=[
            seq_spec,
            pl.BlockSpec((1, 1, K, K), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, K), jnp.float32),
            jax.ShapeDtypeStruct((B, H, K, K), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, K), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, lw, u, state0)
    return y, sout
