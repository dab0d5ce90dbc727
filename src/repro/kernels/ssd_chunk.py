"""Chunked Mamba2 SSD scan — Pallas TPU kernel.

Same chunked-recurrence structure as rwkv6_chunk but with scalar-per-head
decay, which collapses the exponent-difference tensor to a cheap (C, C)
matrix per head: the whole intra-chunk contribution is
``(C·Bᵀ ⊙ L) @ (dt·x)`` — two MXU matmuls. The (N, P) state persists in
VMEM scratch across the sequential chunk grid dimension.

B and C come in G groups; head h reads group h // (H/G) through the
index maps, so no per-head copy of them is made. Every block's last two
dimensions are a whole array dimension or a multiple of the TPU's (8,
128) tile: the operands are laid out head-major, the log-decays as one
row per head, and B as (N, S), so that each product in the kernel is a
plain or a right-transposed matmul. The cumulative sums are matmuls with
triangular ones, in full float32 like the rest.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.dot(a, b, precision=_HI, preferred_element_type=jnp.float32)


def _mm_t(a, b):
    """a @ bᵀ."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _ssd_kernel(dx_ref, la_ref, bt_ref, c_ref, h0_ref, y_ref, hout_ref,
                state_scr, *, nc: int, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = h0_ref[0, 0].astype(jnp.float32)

    dx = dx_ref[0, 0].astype(jnp.float32)           # (C, P)
    la = la_ref[0, 0].astype(jnp.float32)           # (1, C)
    Bt = bt_ref[0, 0].astype(jnp.float32)           # (N, C)
    Cc = c_ref[0, 0].astype(jnp.float32)            # (C, N)
    state = state_scr[...]                          # (N, P)

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = s_idx <= t_idx
    cum_row = _mm(la, jnp.where(t_idx <= s_idx, 1.0, 0.0))  # (1, C) inclusive
    cum_col = _mm_t(jnp.where(causal, 1.0, 0.0), la)        # (C, 1)
    last = jnp.sum(la, axis=1, keepdims=True)       # (1, 1)
    L = jnp.where(causal, jnp.exp(cum_col - cum_row), 0.0)
    y = _mm(_mm(Cc, Bt) * L, dx)                    # intra-chunk
    y = y + _mm(Cc * jnp.exp(cum_col), state)       # from the carried state
    y_ref[0, 0] = y.astype(y_ref.dtype)

    rdec = jnp.exp(last - cum_row)                  # (1, C)
    state_new = jnp.exp(last) * state + _mm(Bt * rdec, dx)
    state_scr[...] = state_new

    @pl.when(ic == nc - 1)
    def _finalize():
        hout_ref[0, 0] = state_new.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunked(dx, la, Bm, Cm, h0, *, chunk: int = 256,
                interpret: bool = False):
    """dx = dt·x (B,S,H,P); la (B,S,H) log-decays; Bm,Cm (B,S,G,N);
    h0 (B,H,N,P). S is a multiple of ``chunk``, H of G.

    Returns (y (B,S,H,P) f32, final state (B,H,N,P) f32)."""
    B, S, H, P = dx.shape
    G, N = Bm.shape[-2:]
    assert S % chunk == 0 and H % G == 0, (S, chunk, H, G)
    nc, per = S // chunk, H // G
    dx_h = dx.transpose(0, 2, 1, 3)                           # (B,H,S,P)
    la_h = la.transpose(0, 2, 1)[:, :, None, :]               # (B,H,1,S)
    bt = Bm.transpose(0, 2, 3, 1)                             # (B,G,N,S)
    c = Cm.transpose(0, 2, 1, 3)                              # (B,G,S,N)
    kernel = functools.partial(_ssd_kernel, nc=nc, chunk=chunk)
    y, hout = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, ic: (b, h, 0, ic)),
            pl.BlockSpec((1, 1, N, chunk),
                         lambda b, h, ic: (b, h // per, 0, ic)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, ic: (b, h // per, ic, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_chunk",
    )(dx_h, la_h, bt, c, h0)
    return y.transpose(0, 2, 1, 3), hout
