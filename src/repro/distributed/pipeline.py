"""Pipeline parallelism: GPipe-style microbatched stage execution.

This is the data plane that G-TRAC's control plane routes over: a served
model is split into contiguous layer *stages*; each stage replica lives on a
device group. Two execution modes:

* ``pipeline_shard_map`` — SPMD pipeline over a dedicated ``stage`` mesh
  axis: every stage holds its layer shard; microbatch activations rotate via
  ``jax.lax.ppermute`` (the TPU analogue of the paper's peer-to-peer
  activation handoff — each handover is one ICI hop instead of an HTTP
  POST). Bubble fraction = (S-1)/(M+S-1) for S stages / M microbatches.
* ``StagePartition`` — the contiguous layer ranges of the stages; each
  model family slices its own params and runs a stage's forward
  (``models/api.Model.served_stages`` and ``stage_forward``), and the
  G-TRAC chain executor drives one jitted stage fn per hop.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


# ---------------------------------------------------------------------------
# Stage partitioning of a layer-stacked param tree
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class StagePartition:
    """Contiguous layer segments [start, end) covering the model."""

    boundaries: Tuple[int, ...]          # len = n_stages + 1; [0, ..., L]

    @property
    def n_stages(self) -> int:
        return len(self.boundaries) - 1

    def segment(self, i: int) -> Tuple[int, int]:
        return self.boundaries[i], self.boundaries[i + 1]

    @staticmethod
    def uniform(num_layers: int, layers_per_stage: int) -> "StagePartition":
        bs = list(range(0, num_layers, layers_per_stage)) + [num_layers]
        return StagePartition(tuple(dict.fromkeys(bs)))


# ---------------------------------------------------------------------------
# shard_map SPMD pipeline (ppermute microbatching)
# ---------------------------------------------------------------------------


def pipeline_shard_map(stage_fn: Callable, mesh: Mesh, n_microbatches: int,
                       stage_axis: str = "stage"):
    """Build a pipelined forward: x (M*b, ...) -> y (M*b, ...).

    ``stage_fn(stage_id, x_mb)`` applies one stage's compute. GPipe
    schedule: M microbatches flow through S stages in M + S - 1 ticks;
    activations advance one stage per tick via ppermute. XLA overlaps the
    permute with the next tick's compute (async collective start/done).
    """
    S = dict(zip(mesh.axis_names, mesh.devices.shape))[stage_axis]

    def pipelined(x):
        def per_stage(x_local):
            # x_local: (M, b, ...) microbatches resident on this stage
            stage = jax.lax.axis_index(stage_axis)
            M = x_local.shape[0]
            n_ticks = M + S - 1
            perm = [(i, (i + 1) % S) for i in range(S)]

            def tick(carry, t):
                buf, out = carry
                # stage 0 injects microbatch t; others use the incoming buf
                mb_idx = jnp.clip(t, 0, M - 1)
                inject = x_local[mb_idx]
                cur = jnp.where(stage == 0, inject, buf)
                y = stage_fn(stage, cur)
                # stage s finishes microbatch (t - s); last stage records it
                done_idx = t - (S - 1)
                write = (stage == S - 1) & (done_idx >= 0)
                out = jnp.where(
                    write,
                    jax.lax.dynamic_update_index_in_dim(
                        out, y, jnp.clip(done_idx, 0, M - 1), 0),
                    out)
                buf = jax.lax.ppermute(y, stage_axis, perm)
                return (buf, out), None

            buf0 = jnp.zeros_like(x_local[0])
            out0 = jnp.zeros_like(x_local)
            (_, out), _ = jax.lax.scan(tick, (buf0, out0),
                                       jnp.arange(n_ticks))
            # results live on the last stage (others hold zeros);
            # psum replicates them so out_specs=P(None...) is honest
            return jax.lax.psum(out, stage_axis)

        # microbatches replicated per stage group
        return jax.shard_map(per_stage, mesh=mesh,
                             in_specs=P(*([None] * x.ndim)),
                             out_specs=P(*([None] * x.ndim)),
                             check_vma=False)(x)

    return pipelined


def pipeline_bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
