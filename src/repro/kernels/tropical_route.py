"""Batched min-plus (tropical) routing DP — Pallas TPU kernel.

This is the paper's Dijkstra-after-pruning, restructured for TPU (DESIGN.md
§2): on the trust-pruned *layered* DAG the shortest path is one min-plus
relaxation per layer boundary,

    d[b] = min_p { d[start_p] + C_p  :  end_p == b } ,

and a batch of R concurrent requests (each with its own pruned cost row)
relaxes in lockstep. The boundary gather ``d[start_p]`` is expressed as a
dense one-hot matmul ``dist @ S`` (S[j,p] = [start_p == j]) so it runs on
the MXU instead of a serial gather — the TPU-native trick that makes the
whole DP two matmuls + a masked min per boundary.

Grid = (R / blk_r,); each program keeps its (blk_r, L+1) distance block and
predecessor block in VMEM for the entire DP (L ≤ a few hundred boundaries —
tiny), streaming nothing back to HBM until the end.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INF = 3.0e38  # python literal: jnp scalars may not be captured by kernels


def _first_argmin(x, m, col):
    """Lowest column index holding the row minimum ``m`` of ``x``.
    ``jnp.argmin``'s Mosaic lowering does not promise the first of tied
    minima; the planners' (value, index) tie order needs it."""
    return jnp.min(jnp.where(x == m[:, None], col, x.shape[1]), axis=1)


def _route_kernel(starts_oh_ref, ends_ref, costs_ref, dist_ref, pred_ref, *,
                  total_layers: int):
    L = total_layers
    S = starts_oh_ref[...]                     # (L+1, P) one-hot f32
    ends = ends_ref[...]                       # (1, P) i32
    costs = costs_ref[...]                     # (blk_r, P)
    blk_r = costs.shape[0]

    # boundary 0 starts at distance 0: an iota mask, not ``.at[].set`` —
    # Mosaic has no scatter lowering
    dist0 = jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (blk_r, L + 1), 1) == 0,
        0.0, INF).astype(jnp.float32)
    pred0 = jnp.full((blk_r, L + 1), -1, jnp.int32)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, costs.shape[1]), 1)

    def body(b, carry):
        dist, pred = carry
        # d[start_p] for all p, via MXU: (blk_r, L+1) @ (L+1, P). HIGHEST:
        # the MXU's default rounds f32 operands to bf16, which corrupts
        # distances; at f32 precision a one-hot gather is exact
        d_start = jax.lax.dot_general(
            jnp.minimum(dist, INF), S, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        cand = jnp.where(ends == b, d_start + costs, INF)   # (blk_r, P)
        best = jnp.min(cand, axis=1)
        arg = _first_argmin(cand, best, col)
        onehot_b = (jax.lax.iota(jnp.int32, L + 1) == b)[None, :]
        dist = jnp.where(onehot_b, best[:, None], dist)
        pred = jnp.where(onehot_b & (best < INF)[:, None], arg[:, None], pred)
        return dist, pred

    dist, pred = jax.lax.fori_loop(1, L + 1, body, (dist0, pred0))
    dist_ref[...] = dist
    pred_ref[...] = pred


@functools.partial(jax.jit, static_argnames=("total_layers", "blk_r",
                                             "interpret"))
def tropical_route(starts, ends, costs, *, total_layers: int,
                   blk_r: int = 64, interpret: bool = False):
    """starts/ends (P,) i32; costs (R, P) f32 (INF = pruned).

    Returns (dist (R, L+1), pred (R, L+1) int32 peer index or -1).

    R need not be a multiple of ``blk_r``: the request batch is padded to
    the next block boundary with all-INF cost rows (whose DP result is the
    infeasible vector — harmless) and the outputs sliced back to R rows.
    """
    R, P = costs.shape
    L = total_layers
    if R == 0:                  # degenerate batch: nothing to route
        return (jnp.full((0, L + 1), INF, jnp.float32),
                jnp.full((0, L + 1), -1, jnp.int32))
    blk_r = min(blk_r, R)
    r_pad = (-R) % blk_r
    if r_pad:
        costs = jnp.concatenate(
            [costs, jnp.full((r_pad, P), INF, costs.dtype)], axis=0)
    r_total = R + r_pad
    # one-hot boundary matrix, built once outside the kernel
    starts_oh = jax.nn.one_hot(starts, L + 1, dtype=jnp.float32).T  # (L+1, P)
    kernel = functools.partial(_route_kernel, total_layers=L)
    dist, pred = pl.pallas_call(
        kernel,
        grid=(r_total // blk_r,),
        in_specs=[
            pl.BlockSpec((L + 1, P), lambda i: (0, 0)),
            pl.BlockSpec((1, P), lambda i: (0, 0)),
            pl.BlockSpec((blk_r, P), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((blk_r, L + 1), lambda i: (i, 0)),
            pl.BlockSpec((blk_r, L + 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r_total, L + 1), jnp.float32),
            jax.ShapeDtypeStruct((r_total, L + 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(starts_oh, ends[None, :].astype(jnp.int32), costs)
    if r_pad:
        dist, pred = dist[:R], pred[:R]
    return dist, pred


# ---------------------------------------------------------------------------
# K-best variant: top-K (dist, pred, rank) per boundary
# ---------------------------------------------------------------------------


def _route_kernel_kbest(starts_oh_ref, ends_ref, costs_ref, dist_ref,
                        pedge_ref, prank_ref, *, total_layers: int,
                        k_best: int):
    """K-best min-plus DP, 2-D layout: the K alternates of each boundary
    live in K adjacent columns (column b*K + k = boundary b, rank k), so
    the boundary gather stays ONE MXU matmul against the Kronecker one-hot
    ``S ⊗ I_K`` and the per-boundary top-K reduction is K rounds of
    (min, argmin, mask) over the (blk_r, P*K) candidate block — the same
    (value, peer, rank) tie order as the numpy planner DP's stable sort.
    """
    L, K = total_layers, k_best
    S = starts_oh_ref[...]                     # ((L+1)*K, P*K) f32
    ends = ends_ref[...]                       # (1, P*K) i32, K-replicated
    costs = costs_ref[...]                     # (blk_r, P*K), K-replicated
    blk_r, PK = costs.shape

    dist0 = jnp.where(                         # boundary 0, rank 0
        jax.lax.broadcasted_iota(jnp.int32, (blk_r, (L + 1) * K), 1) == 0,
        0.0, INF).astype(jnp.float32)
    pedge0 = jnp.full((blk_r, (L + 1) * K), -1, jnp.int32)
    prank0 = jnp.full((blk_r, (L + 1) * K), -1, jnp.int32)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, PK), 1)
    outcol = jax.lax.broadcasted_iota(jnp.int32, (1, (L + 1) * K), 1)

    def body(b, carry):
        dist, pedge, prank = carry
        d_start = jax.lax.dot_general(                  # exact: HIGHEST
            jnp.minimum(dist, INF), S, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        cand = jnp.where(ends == b, d_start + costs, INF)   # (blk_r, PK)
        for k in range(K):
            m = jnp.min(cand, axis=1)
            a = _first_argmin(cand, m, col)
            ok = (m < INF)[:, None]
            tgt = outcol == b * K + k
            dist = jnp.where(tgt, jnp.where(ok, m[:, None], INF), dist)
            pedge = jnp.where(tgt & ok, (a // K)[:, None], pedge)
            prank = jnp.where(tgt & ok, (a % K)[:, None], prank)
            cand = jnp.where(col == a[:, None], INF, cand)
        return dist, pedge, prank

    dist, pedge, prank = jax.lax.fori_loop(1, L + 1, body,
                                           (dist0, pedge0, prank0))
    dist_ref[...] = dist
    pedge_ref[...] = pedge
    prank_ref[...] = prank


@functools.partial(jax.jit, static_argnames=("total_layers", "k_best",
                                             "blk_r", "interpret"))
def tropical_route_kbest(starts, ends, costs, *, total_layers: int,
                         k_best: int, blk_r: int = 64,
                         interpret: bool = False):
    """K-best batched routing DP. starts/ends (P,) i32; costs (R, P) f32.

    Returns (distK (R, L+1, K) f32, pedge (R, L+1, K) i32 peer index or
    -1, prank (R, L+1, K) i32 predecessor rank or -1) — exactly what
    ``core.routing_jax.backtrack_kbest`` consumes, and bit-for-bit the
    output of ``core.routing_jax.layered_dp_kbest``. Empty batches
    (R == 0) return empty outputs instead of dividing by zero in the
    grid computation.
    """
    R, P = costs.shape
    L, K = total_layers, k_best
    if R == 0:                  # degenerate batch: nothing to route
        return (jnp.full((0, L + 1, K), INF, jnp.float32),
                jnp.full((0, L + 1, K), -1, jnp.int32),
                jnp.full((0, L + 1, K), -1, jnp.int32))
    blk_r = min(blk_r, R)
    r_pad = (-R) % blk_r
    if r_pad:
        costs = jnp.concatenate(
            [costs, jnp.full((r_pad, P), INF, costs.dtype)], axis=0)
    r_total = R + r_pad
    # Kronecker one-hot (S ⊗ I_K): row j*K+k routes dist[j, rank k] to
    # every peer column p*K+k with start_p == j, built once outside
    starts_oh = jax.nn.one_hot(starts, L + 1, dtype=jnp.float32).T
    starts_oh = jnp.kron(starts_oh, jnp.eye(K, dtype=jnp.float32))
    ends_rep = jnp.repeat(ends.astype(jnp.int32), K)[None, :]
    costs_rep = jnp.repeat(costs, K, axis=1)
    kernel = functools.partial(_route_kernel_kbest, total_layers=L,
                               k_best=K)
    dist, pedge, prank = pl.pallas_call(
        kernel,
        grid=(r_total // blk_r,),
        in_specs=[
            pl.BlockSpec(((L + 1) * K, P * K), lambda i: (0, 0)),
            pl.BlockSpec((1, P * K), lambda i: (0, 0)),
            pl.BlockSpec((blk_r, P * K), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((blk_r, (L + 1) * K), lambda i: (i, 0)),
            pl.BlockSpec((blk_r, (L + 1) * K), lambda i: (i, 0)),
            pl.BlockSpec((blk_r, (L + 1) * K), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r_total, (L + 1) * K), jnp.float32),
            jax.ShapeDtypeStruct((r_total, (L + 1) * K), jnp.int32),
            jax.ShapeDtypeStruct((r_total, (L + 1) * K), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(starts_oh, ends_rep, costs_rep)
    if r_pad:
        dist, pedge, prank = dist[:R], pedge[:R], prank[:R]
    return (dist.reshape(R, L + 1, K), pedge.reshape(R, L + 1, K),
            prank.reshape(R, L + 1, K))
