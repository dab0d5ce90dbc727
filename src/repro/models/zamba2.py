"""Zamba2 — a Mamba2 stack in which the layers ``cfg.hybrid_layer_ids``
first run one of ``cfg.num_mem_blocks`` SHARED attention+MLP blocks, in
turn, on the hidden state beside the original token embeddings.

With x the residual stream and x0 = embed(tokens), unscaled:

* mamba layer ℓ:  x <- x + Mamba2_ℓ(RMS(x))
* hybrid layer ℓ, the r-th of them, with block b = r mod num_mem_blocks:
  u = linear_ℓ(T_b(x, x0)), then x <- x + Mamba2_ℓ(RMS(x + u))
* T_b(x, x0): c = RMS([x ; x0]) (2d wide); a = o_proj(Attn(c)), causal,
  RoPE on q and k, scaled by (head_dim/2)^-1/2; then
  down(gelu(gate) * up) with [gate ; up] = gate_up(RMS(a)) + B_ℓ(A_ℓ(
  RMS(a))), the layer's own rank-``adapter_rank`` LoRA. No residual
  inside the block.
* head: the final RMS, then the head tied to the embedding.

Each shared block has ONE weight copy (attention weights amortised across
the depth); each hybrid layer keeps its own KV cache. Which layers are
hybrid, and which block each uses, is static.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn, mamba2 as m2, mlp
from repro.models.common import (
    EMBEDDING_TABLES,
    Params,
    adtype,
    apply_norm,
    cast_roles,
    chunked_cross_entropy,
    cross_entropy_loss,
    dense_init,
    embed_tokens,
    init_embeddings,
    init_norm,
    logits_head,
    pdtype,
    scan_or_unroll,
    split_keys,
)
from repro.models.rope import apply_rotary, positional_angles

#: a hybrid layer's own weights, consumed at the activation dtype
HYBRID_WEIGHTS = ("linear", "lora_a", "lora_b")


def init(key, cfg: ModelConfig) -> Params:
    kemb, kmamba, kshared, khybrid = jax.random.split(key, 4)

    def init_mamba(k):
        return {"mixer": m2.init_mamba2(k, cfg), "norm": init_norm(cfg)}

    def init_shared(k):
        ks = split_keys(k, ["attn", "mlp"])
        return {"norm": init_norm(cfg, 2 * cfg.d_model),
                "attn": attn.init_attention(ks["attn"], cfg,
                                            d_in=2 * cfg.d_model),
                "mlp_norm": init_norm(cfg),
                "mlp": mlp.init_mlp(ks["mlp"], cfg)}

    def init_hybrid(k):
        d, r, pd = cfg.d_model, cfg.adapter_rank, pdtype(cfg)
        ks = split_keys(k, HYBRID_WEIGHTS)
        return {"linear": dense_init(ks["linear"], (d, d), dtype=pd),
                "lora_a": dense_init(ks["lora_a"], (d, r), dtype=pd),
                "lora_b": dense_init(ks["lora_b"], (r, 2 * cfg.d_ff),
                                     dtype=pd)}

    return {
        "embed": init_embeddings(kemb, cfg),
        "mamba": jax.vmap(init_mamba)(jax.random.split(kmamba,
                                                       cfg.num_layers)),
        "shared": jax.vmap(init_shared)(jax.random.split(
            kshared, cfg.num_mem_blocks)),
        "hybrid": jax.vmap(init_hybrid)(jax.random.split(
            khybrid, len(cfg.hybrid_layer_ids))),
        "final_norm": init_norm(cfg),
    }


def activation_dtype_params(cfg: ModelConfig, params: Params) -> Params:
    """``params`` with the matmul weights (mixers' projections and conv,
    shared blocks' attention and MLP, hybrid layers' linears and LoRAs)
    and the embedding table cast to the activation dtype, and A_log, D,
    dt_bias, the norms and the conv bias as stored. A leaf's role is its
    name under its parent, as in ``transformer.activation_dtype_params``."""
    dt = adtype(cfg)
    if dt == pdtype(cfg):
        return params
    return cast_roles(params, {"embed": EMBEDDING_TABLES,
                               "mixer": m2.MATMUL_WEIGHTS,
                               "attn": attn.MATMUL_WEIGHTS,
                               "mlp": mlp.MATMUL_WEIGHTS,
                               "hybrid": HYBRID_WEIGHTS}, dt)


def _at(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def hybrids(cfg: ModelConfig, params: Params) -> Dict[int, Params]:
    """The hybrid layers, keyed by layer: each its own weights and the
    shared block it uses."""
    return {l: {**_at(params["hybrid"], r),
                "block": _at(params["shared"], r % cfg.num_mem_blocks)}
            for r, l in enumerate(cfg.hybrid_layer_ids)}


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------


def _shared_in(cfg, bp, x, x0, angles):
    c = apply_norm(cfg, bp["norm"], jnp.concatenate([x, x0], axis=-1))
    q, k, v = attn.qkv_proj(cfg, bp["attn"], c)
    return apply_rotary(q, angles), apply_rotary(k, angles), v


def _shared_out(cfg, hp, o):
    """o_proj, the MLP with the layer's LoRA, then the layer's linear."""
    bp = hp["block"]
    a = attn.out_proj(cfg, bp["attn"], o)
    h = apply_norm(cfg, bp["mlp_norm"], a)
    dt = h.dtype
    lora = (h @ hp["lora_a"].astype(dt)) @ hp["lora_b"].astype(dt)
    f = cfg.d_ff
    m = mlp.apply_mlp(cfg, bp["mlp"], h, delta=(lora[..., :f], lora[..., f:]))
    return m @ hp["linear"].astype(dt)


def _scale(cfg):
    return (cfg.head_dim / 2) ** -0.5


def hybrid_input(cfg, hp, x, x0, angles):
    """u = linear(T_b(x, x0)) over a whole sequence, and the block's k, v."""
    q, k, v = _shared_in(cfg, hp["block"], x, x0, angles)
    o = attn.attend(cfg, q, k, v, causal=True, scale=_scale(cfg))
    return _shared_out(cfg, hp, o), (k, v)


def run_layers(cfg: ModelConfig, mamba: Params, hyb: Dict[int, Params], x,
               x0, angles, collect: bool = False):
    """The layers of a stacked ``mamba`` slice over a whole sequence, the
    hybrid ones (``hyb``, keyed by index in the slice) first running
    their shared block. One scan over the layers: a hybrid layer picks
    its branch of a switch, every other layer the branch that adds
    nothing, so the program holds one mixer and one copy of each
    hybrid layer's block however deep the slice is. With ``collect``,
    also returns the per-layer (conv, ssm) states stacked, and the
    hybrid layers' (k, v), stacked in layer order."""
    keys = sorted(hyb)
    n = jax.tree.leaves(mamba)[0].shape[0]
    branch = jnp.asarray([keys.index(i) + 1 if i in hyb else 0
                          for i in range(n)], jnp.int32)

    def nothing(x):
        u = jnp.zeros_like(x)
        if not collect:
            return u
        B, S = x.shape[:2]
        kv = jnp.zeros((B, S, cfg.num_kv_heads, cfg.head_dim), x.dtype)
        return u, (kv, kv)

    def block(hp, x):
        with jax.named_scope("zamba2.shared_block"):
            u, kv = hybrid_input(cfg, hp, x, x0, angles)
        return (u, kv) if collect else u

    branches = [nothing] + [functools.partial(block, hyb[k]) for k in keys]

    def body(x, inp):
        lp, b = inp
        out = jax.lax.switch(b, branches, x) if keys else nothing(x)
        u, kv = out if collect else (out, None)
        with jax.named_scope("zamba2.mixer"):
            y, st = m2.mamba2_forward(cfg, lp["mixer"],
                                      apply_norm(cfg, lp["norm"], x + u))
        return x + y, (st, kv) if collect else None

    x, ys = scan_or_unroll(body, x, (mamba, branch), scan=cfg.scan_layers,
                           length=n)
    if not collect:
        return x
    states, (k, v) = ys
    return x, states, (k[jnp.asarray(keys, jnp.int32)],
                       v[jnp.asarray(keys, jnp.int32)])


def _angles(cfg, B, S):
    return positional_angles(cfg, jnp.arange(S)[None, :].repeat(B, 0))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def make_cache(cfg: ModelConfig, batch: int, capacity: int):
    _, H, P, N = m2.dims(cfg)
    kv = (len(cfg.hybrid_layer_ids), batch, capacity, cfg.num_kv_heads,
          cfg.head_dim)
    return {
        "k": jnp.zeros(kv, adtype(cfg)),
        "v": jnp.zeros(kv, adtype(cfg)),
        "conv": jnp.zeros((cfg.num_layers, batch, cfg.ssm_conv_width - 1,
                           m2.conv_channels(cfg)), adtype(cfg)),
        "ssm": jnp.zeros((cfg.num_layers, batch, H, N, P), jnp.float32),
        "index": jnp.zeros((), jnp.int32),
    }


def forward_hidden(cfg, params, tokens):
    """tokens (B,S) -> final-normed hidden (B,S,d), per-layer states and
    per-hybrid-layer (k, v)."""
    B, S = tokens.shape
    x0 = embed_tokens(cfg, params["embed"], tokens)
    x, states, kv = run_layers(cfg, params["mamba"], hybrids(cfg, params),
                               x0, x0, _angles(cfg, B, S), collect=True)
    return apply_norm(cfg, params["final_norm"], x), states, kv


def loss_fn(cfg: ModelConfig, params: Params, batch):
    x, _, _ = forward_hidden(cfg, params, batch["tokens"])
    if cfg.ce_impl == "chunked":
        return chunked_cross_entropy(cfg, params["embed"], x, batch["labels"],
                                     chunk=cfg.ce_chunk,
                                     mask=batch.get("mask"))
    logits = logits_head(cfg, params["embed"], x)
    return cross_entropy_loss(logits, batch["labels"], batch.get("mask"))


def prefill(cfg: ModelConfig, params: Params, tokens, capacity=None, **_):
    S = tokens.shape[1]
    x, (conv, ssm), (k, v) = forward_hidden(cfg, params, tokens)
    capacity = capacity or S
    pad = [(0, 0), (0, 0), (0, capacity - S), (0, 0), (0, 0)]
    cache = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad),
             "conv": conv.astype(adtype(cfg)), "ssm": ssm,
             "index": jnp.asarray(S, jnp.int32)}
    return logits_head(cfg, params["embed"], x[:, -1:, :]), cache


def decode_step(cfg: ModelConfig, params: Params, token, cache, **_):
    index = cache["index"]
    B = token.shape[0]
    x0 = embed_tokens(cfg, params["embed"], token)
    angles = positional_angles(cfg, jnp.full((B, 1), index, jnp.int32))
    hyb = hybrids(cfg, params)
    x, conv, ssm, K, V = x0, [], [], [], []
    for i in range(cfg.num_layers):
        lp = _at(params["mamba"], i)
        h = x
        if i in hyb:
            j = len(K)
            q, k, v = _shared_in(cfg, hyb[i]["block"], x, x0, angles)
            ck, cv = attn.cache_update(cache["k"][j], cache["v"][j], k, v,
                                       index, masked=cfg.decode_masked_write)
            o = attn.decode_attend(cfg, q, ck, cv, index + 1,
                                   scale=_scale(cfg))
            h, K, V = x + _shared_out(cfg, hyb[i], o), K + [ck], V + [cv]
        out, (c, s) = m2.mamba2_step(
            cfg, lp["mixer"], apply_norm(cfg, lp["norm"], h),
            (cache["conv"][i].astype(x.dtype), cache["ssm"][i]))
        x, conv, ssm = x + out, conv + [c.astype(adtype(cfg))], ssm + [s]
    x = apply_norm(cfg, params["final_norm"], x)
    stack = lambda xs, old: jnp.stack(xs) if xs else old
    return logits_head(cfg, params["embed"], x), {
        "k": stack(K, cache["k"]), "v": stack(V, cache["v"]),
        "conv": jnp.stack(conv), "ssm": jnp.stack(ssm), "index": index + 1}


# ---------------------------------------------------------------------------
# Pipeline stages
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg", "size"))
def _served(cfg: ModelConfig, tree, start, *, size: int):
    """``tree``'s stacks at ``[start, start + size)``, or at ``start``
    alone for size 0, cast as ``activation_dtype_params`` says; slice and
    cast fuse, so no float32 copy of a cast slice is made."""
    take = ((lambda a: jax.lax.dynamic_index_in_dim(a, start, keepdims=False))
            if size == 0 else
            (lambda a: jax.lax.dynamic_slice_in_dim(a, start, size)))
    return activation_dtype_params(cfg, jax.tree.map(take, tree))


def served_stages(cfg: ModelConfig, params: Params,
                  segments: Sequence[Tuple[int, int]]) -> List[Params]:
    """Each stage's weights as ``stage_forward`` takes them: its slice of
    the Mamba2 stack; for each hybrid layer in it, keyed by its index in
    the stage, the layer's linear and LoRA and the shared block it uses;
    and the embedding and final norm that all stages share. Matmul
    weights and the embedding are in the activation dtype. Each shared
    block is cast once and the same arrays serve every stage that uses
    it."""
    top = activation_dtype_params(cfg, {"embed": params["embed"],
                                        "final_norm": params["final_norm"]})
    blocks = {}
    stages = []
    for s, e in segments:
        hyb = {}
        for r, l in enumerate(cfg.hybrid_layer_ids):
            if s <= l < e:
                b = r % cfg.num_mem_blocks
                if b not in blocks:
                    blocks[b] = _served(cfg, {"shared": params["shared"]}, b,
                                        size=0)["shared"]
                own = _served(cfg, {"hybrid": params["hybrid"]}, r,
                              size=0)["hybrid"]
                hyb[l - s] = {**own, "block": blocks[b]}
        mamba = _served(cfg, {"mamba": params["mamba"]}, s, size=e - s)
        stages.append({**top, **mamba, "hybrid": hyb})
    return stages


#: the routed path right-pads a hop's positions to a multiple of this.
#: Every layer is causal (conv, scan, attention), so positions past a
#: prefix change nothing in it, and a stage compiles one program per
#: bucket instead of one per prefix length
STAGE_BUCKET = 32


def stage_forward(cfg: ModelConfig, stage_params: Params, tokens, x, *,
                  first: bool, last: bool, length=None):
    """One stage's forward. Stage 0 embeds ``tokens``; a stage that holds
    a hybrid layer embeds them again for its shared block's x0, so the
    hop payload stays ``(tokens, x)``. The last stage returns the logits
    at position ``length - 1`` (the last position when ``length`` is
    None; right-padded ``tokens`` pass their true length) instead of
    hidden states (B, S, d)."""
    B, S = tokens.shape
    hyb = stage_params["hybrid"]
    x0 = (embed_tokens(cfg, stage_params["embed"], tokens)
          if first or hyb else None)
    if first:
        x = x0
    angles = _angles(cfg, B, S) if hyb else None
    x = run_layers(cfg, stage_params["mamba"], hyb, x, x0, angles)
    if last:
        x = (x[:, -1:, :] if length is None else
             jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1))
        x = apply_norm(cfg, stage_params["final_norm"], x)
        return logits_head(cfg, stage_params["embed"], x)
    return x
