"""Chunked Mamba2 SSD scan — Pallas TPU kernel.

Same chunked-recurrence structure as rwkv6_chunk but with scalar-per-head
decay, which collapses the exponent-difference tensor to a cheap (C, C)
matrix per head: the whole intra-chunk contribution is
``(C·Bᵀ ⊙ L) @ (dt·x)`` — two MXU matmuls. The (N, P) state persists in
VMEM scratch across the sequential chunk grid dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, la_ref, b_ref, c_ref, h0_ref, y_ref, hout_ref,
                state_scr, *, nc: int, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = h0_ref[0, 0].astype(jnp.float32)

    xc = x_ref[0, :, 0, :].astype(jnp.float32)      # (C, P)
    dtc = dt_ref[0, :, 0].astype(jnp.float32)       # (C,)
    lac = la_ref[0, :, 0].astype(jnp.float32)       # (C,)
    Bc = b_ref[0].astype(jnp.float32)               # (C, N)
    Cc = c_ref[0].astype(jnp.float32)               # (C, N)
    state = state_scr[...]                          # (N, P)

    cum = jnp.cumsum(lac)                           # (C,)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(s_idx <= t_idx, jnp.exp(cum[:, None] - cum[None, :]), 0.0)
    G = jax.lax.dot_general(Cc, Bc, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (C, C)
    dx = xc * dtc[:, None]                          # (C, P)
    y = jax.lax.dot_general(G * L, dx, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = y + jax.lax.dot_general(Cc * jnp.exp(cum)[:, None], state,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    rdec = jnp.exp(cum[-1] - cum)                   # (C,) — dt is already in dx
    state_new = jnp.exp(cum[-1]) * state + jax.lax.dot_general(
        Bc * rdec[:, None], dx, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    state_scr[...] = state_new

    @pl.when(ic == nc - 1)
    def _finalize():
        hout_ref[0, 0] = state_new.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunked(x, dt, la, Bm, Cm, h0, *, chunk: int = 64,
                interpret: bool = False):
    """x (B,S,H,P); dt,la (B,S,H); Bm,Cm (B,S,N); h0 (B,H,N,P).

    Returns (y (B,S,H,P) f32, final state (B,H,N,P) f32)."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    kernel = functools.partial(_ssd_kernel, nc=nc, chunk=chunk)
    y, hout = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, ic: (b, ic, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, ic: (b, ic, h)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, ic: (b, ic, h)),
            pl.BlockSpec((1, chunk, N), lambda b, h, ic: (b, ic, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, ic: (b, ic, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, ic: (b, ic, h, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, la, Bm, Cm, h0)
    return y, hout
