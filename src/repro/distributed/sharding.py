"""Sharding rules: parameter / batch / cache PartitionSpecs per mesh.

Strategy (defaults — the §Perf hillclimb mutates these):

* **FSDP × TP**: every weight matrix shards its d_model-side dimension on
  ``data`` (FSDP: gathered per scan step, which XLA overlaps with compute)
  and its wide output dimension (heads / d_ff / vocab / experts) on
  ``model`` (tensor parallelism). 256-way parameter sharding is what lets
  granite-34b's optimizer state fit 16 GB HBM chips.
* **Batch** shards on ``("pod", "data")`` (pure DP across pods).
* **KV caches** shard batch on ``data`` and heads on ``model`` when the
  arch has ≥ model-axis KV heads; otherwise (MQA, batch-1 long-context)
  they shard the *sequence* dimension on ``model`` — the sequence-parallel
  decode path (GSPMD inserts the partial-softmax combine).

Rules are name-based over the params pytree (tree_map_with_path), so any new
module participates by following the repo's naming conventions.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def dp_axes(mesh: Mesh):
    """Batch data-parallel axes: ('pod','data') on multi-pod meshes."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


# ---------------------------------------------------------------------------
# Parameter rules
# ---------------------------------------------------------------------------

# keys whose arrays are small / 1-D and stay replicated
_REPLICATED = {"weight", "bias", "mu", "cm_mu", "w0", "u", "gn_w", "gn_b",
               "A_log", "D", "dt_bias", "conv_b"}
# (d_model, wide) matrices: shard in-dim on data (FSDP), out-dim on model (TP)
_IN_DATA_OUT_MODEL = {"wq", "wk", "wv", "wi", "wg", "wr", "wd1",
                      "cm_k", "cm_r", "in_proj"}
# (wide, d_model): transpose of the above
_IN_MODEL_OUT_DATA = {"wo", "cm_v", "out_proj", "wd2"}


def _pspec_for(key: str, shape: Tuple[int, ...], stacked: bool) -> P:
    """PartitionSpec for a leaf named ``key``; ``stacked`` = leading layer
    axis present (scan-over-layers stacking)."""
    lead = (None,) if stacked else ()
    nd = len(shape) - len(lead)
    if key in _REPLICATED or nd <= 1:
        return P(*lead, *([None] * nd))
    if key == "tok" or key == "head":            # (V, d): vocab on model
        return P("model", "data")
    if key == "pos" or key == "enc_pos":         # (S, d)
        return P(None, "data")
    if key == "router":                          # (d, E)
        return P(*lead, "data", None)
    if key in ("wi", "wg", "wo") and nd == 3:    # MoE (E, d, f)/(E, f, d)
        return P(*lead, "model", "data", None) if key != "wo" else \
            P(*lead, "model", None, "data")
    if key == "conv_w":                          # (W, Ch)
        return P(*lead, None, "model")
    if key in _IN_DATA_OUT_MODEL:
        return P(*lead, "data", "model")
    if key in _IN_MODEL_OUT_DATA:
        return P(*lead, "model", "data")
    # default: replicate
    return P(*lead, *([None] * nd))


_STACKED_ROOTS = {"layers", "mamba", "shared", "hybrid", "encoder", "decoder"}


def param_pspecs(params, serving: bool = False) -> object:
    """PartitionSpec pytree matching ``params``.

    ``serving=True`` strips the FSDP ('data') component: weights stay
    TP-sharded on 'model' but fully resident per data-parallel group, so a
    decode step does ZERO weight gathers. FSDP layouts amortise gathers over
    thousands of tokens per step in training; at one token per step they are
    pure collective overhead (the decode hillclimb in EXPERIMENTS.md §Perf).
    """
    def spec(path, leaf):
        keys = [p.key for p in path if hasattr(p, "key")]
        stacked = bool(keys) and keys[0] in _STACKED_ROOTS
        ps = _pspec_for(keys[-1], leaf.shape, stacked)
        if serving:
            ps = P(*[None if ax == "data" else ax for ax in ps])
        return ps

    return jax.tree_util.tree_map_with_path(spec, params)


def param_shardings(mesh: Mesh, params, serving: bool = False) -> object:
    specs = fit_pspecs(mesh, param_pspecs(params, serving=serving), params)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def fit_pspecs(mesh: Mesh, specs, tree):
    """Drop spec axes whose dimension is not divisible by the mesh axis —
    pjit argument shardings require exact divisibility (e.g. whisper's
    vocab 51866 cannot shard 16-way and falls back to replicated)."""
    def fit(spec, leaf):
        if not isinstance(spec, P):
            return spec
        out = []
        for dim, ax in enumerate(spec):
            if ax is None:
                out.append(None)
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = int(np.prod([mesh_axis_size(mesh, a) for a in axes]))
            out.append(ax if leaf.shape[dim] % size == 0 else None)
        return P(*out)

    return jax.tree.map(fit, specs, tree,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Batch / input rules
# ---------------------------------------------------------------------------


def batch_pspecs(mesh: Mesh, batch) -> object:
    dp = dp_axes(mesh)

    dp_size = int(np.prod([mesh_axis_size(mesh, a) for a in dp]))

    def spec(path, leaf):
        keys = [p.key for p in path if hasattr(p, "key")]
        name = keys[-1] if keys else ""
        nd = len(leaf.shape)
        if name == "positions" and nd == 3:      # (3, B, S)
            b = leaf.shape[1]
            return P(None, dp if b % dp_size == 0 else None, None)
        if nd == 0:
            return P()
        rest = [None] * (nd - 1)
        if leaf.shape[0] % dp_size != 0:         # tiny batch: replicate
            return P(None, *rest)
        return P(dp, *rest)                      # batch-major inputs

    return jax.tree_util.tree_map_with_path(spec, batch)


# ---------------------------------------------------------------------------
# Cache rules (decode / serve_step)
# ---------------------------------------------------------------------------


def cache_pspecs(mesh: Mesh, cfg: ModelConfig, cache) -> object:
    """Decode-cache specs. KV tensors are (L_or_G, B, S, Hkv, D)."""
    dp = dp_axes(mesh)
    model_size = mesh_axis_size(mesh, "model")
    batch = None
    for leaf in jax.tree_util.tree_leaves(cache):
        if hasattr(leaf, "ndim") and leaf.ndim >= 2:
            batch = leaf.shape[1]
            break
    # heads need exact divisibility (pjit) — 20 heads on a 16-way model
    # axis falls through to sequence sharding instead of replicating
    heads_shardable = (cfg.num_kv_heads >= model_size
                       and cfg.num_kv_heads % model_size == 0)
    batch_shardable = batch is None or batch >= int(np.prod(
        [mesh_axis_size(mesh, a) for a in dp]))

    def kv_spec():
        if heads_shardable and batch_shardable:
            return P(None, dp, None, "model", None)
        if heads_shardable:      # batch-1 long context: SP over data + TP heads
            return P(None, None, "data", "model", None)
        if batch_shardable:      # MQA: sequence-parallel over model
            # GSPMD inserts the partial-softmax combine over 'model'
            return P(None, dp, "model", None, None)
        return P(None, None, ("data", "model"), None, None)

    def spec(path, leaf):
        keys = [p.key for p in path if hasattr(p, "key")]
        name = keys[-1] if keys else ""
        nd = leaf.ndim
        if name in ("k", "v", "sk", "sv", "ck", "cv") and nd == 5:
            return kv_spec()
        if name == "index" or nd == 0:
            return P()
        if name == "wkv" and nd == 5:            # (L, B, H, K, V)
            return P(None, dp if batch_shardable else None, "model", None,
                     None)
        if name == "ssm" and nd == 5:            # (L, B, H, N, P)
            return P(None, dp if batch_shardable else None, "model", None,
                     None)
        if name == "conv" and nd == 4:           # (L, B, W-1, Ch)
            return P(None, dp if batch_shardable else None, None, "model")
        if name in ("tm_last", "cm_last") and nd == 3:   # (L, B, d)
            return P(None, dp if batch_shardable else None, "model")
        rest = [None] * (nd - 1)
        return P(None, *rest)

    return jax.tree_util.tree_map_with_path(spec, cache)


def logits_pspec(mesh: Mesh, batch_shardable: bool = True) -> P:
    dp = dp_axes(mesh)
    return P(dp if batch_shardable else None, None, "model")


# ---------------------------------------------------------------------------
# Activation sharding constraints (logical axes)
# ---------------------------------------------------------------------------
#
# Without explicit constraints GSPMD must arbitrate the FSDP-vs-DP conflict
# (weights shard d_model on 'data', activations shard batch on 'data') and
# empirically resolves it by UNSHARDING THE BATCH — replicating every score/
# logit tensor 16× (the 2.5 TB/device failure observed in the first dry-run).
# ``constrain(x, ...logical axes)`` pins the MaxText-style layout: batch on
# ('pod','data'), heads/ff/vocab/experts on 'model'. It is a no-op outside a
# policy context so model code runs unmodified on a single CPU device.

_POLICY: dict = {"mesh": None}

_LOGICAL = {
    "batch": "__dp__",       # resolved to ('pod','data') / ('data',)
    "heads": "model",
    "ff": "model",
    "vocab": "model",
    "expert": "model",
    "seq": None,
    "seq_model": "model",    # sequence-parallel attention (decode SP)
    "embed": None,
    None: None,
}


def set_activation_policy(mesh: Optional[Mesh]) -> None:
    _POLICY["mesh"] = mesh


class activation_policy:
    """Context manager: with activation_policy(mesh): ... lower/compile ..."""

    def __init__(self, mesh):
        self.mesh = mesh

    def __enter__(self):
        set_activation_policy(self.mesh)
        return self.mesh

    def __exit__(self, *exc):
        set_activation_policy(None)
        return False


def constrain(x, *logical):
    """Apply with_sharding_constraint per the logical-axis names (or None)."""
    mesh = _POLICY["mesh"]
    if mesh is None:
        return x
    dp = dp_axes(mesh)
    dp_size = int(np.prod([mesh_axis_size(mesh, a) for a in dp]))
    assert len(logical) == x.ndim, (logical, x.shape)
    spec = []
    for dim, name in enumerate(logical):
        ax = _LOGICAL.get(name)
        if ax == "__dp__":
            spec.append(dp if x.shape[dim] % dp_size == 0 else None)
        elif ax is not None and \
                x.shape[dim] % mesh_axis_size(mesh, ax) == 0:
            spec.append(ax)
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))
