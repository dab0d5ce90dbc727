"""Pure-jnp oracles for every Pallas kernel (the ground truth the kernels
are validated against in interpret mode — see tests/test_kernels_*.py)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Attention oracles
# ---------------------------------------------------------------------------


def attention_ref(q, k, v, *, causal: bool = True):
    """Naive full-softmax GQA attention. q (B,S,Hq,D); k,v (B,S,Hkv,D)."""
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, D).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scale = 1.0 / np.sqrt(D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kf) * scale
    if causal:
        mask = jnp.tril(jnp.ones((Sq, k.shape[1]), bool))
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, vf)
    return o.reshape(B, Sq, Hq, D).astype(q.dtype)


def decode_attention_ref(q, cache_k, cache_v, kv_len):
    """q (B,Hq,D); caches (B,S,Hkv,D); kv_len (B,) -> (B,Hq,D)."""
    B, Hq, D = q.shape
    S, Hkv = cache_k.shape[1], cache_k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, D).astype(jnp.float32)
    kf = cache_k.astype(jnp.float32)
    vf = cache_v.astype(jnp.float32)
    scale = 1.0 / np.sqrt(D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, kf) * scale
    live = jnp.arange(S)[None, :] < kv_len[:, None]          # (B, S)
    s = jnp.where(live[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, vf)
    return o.reshape(B, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Tropical (min-plus) routing oracle
# ---------------------------------------------------------------------------


def tropical_route_ref(starts, ends, costs, total_layers: int):
    """Layered-DAG min-plus DP, numpy reference.

    starts/ends (P,), costs (R,P) with INF-pruned entries.
    Returns (dist (R, L+1), pred (R, L+1))."""
    starts = np.asarray(starts)
    ends = np.asarray(ends)
    costs = np.asarray(costs, np.float32)
    R, P = costs.shape
    L = total_layers
    INF = np.float32(3.0e38)
    dist = np.full((R, L + 1), INF, np.float32)
    pred = np.full((R, L + 1), -1, np.int32)
    dist[:, 0] = 0.0
    for b in range(1, L + 1):
        mask = ends == b
        if not mask.any():
            continue
        with np.errstate(over="ignore"):  # INF + INF -> inf is intended
            cand = np.where(mask[None, :], dist[:, starts] + costs, INF)
        best = cand.min(axis=1)
        arg = cand.argmin(axis=1)
        dist[:, b] = best
        pred[:, b] = np.where(best < INF, arg, -1)
    return dist, pred


def tropical_route_kbest_ref(starts, ends, costs, total_layers: int,
                             k_best: int):
    """K-best layered-DAG min-plus DP, numpy reference.

    Per boundary the (P, K) extension candidates are reduced with a stable
    sort by (value, peer index, rank) — the tie order shared by
    ``routing_jax.layered_dp_kbest`` and the Pallas kernel. Returns
    (distK (R, L+1, K), pedge (R, L+1, K), prank (R, L+1, K))."""
    starts = np.asarray(starts)
    ends = np.asarray(ends)
    costs = np.asarray(costs, np.float32)
    R, P = costs.shape
    L, K = total_layers, k_best
    INF = np.float32(3.0e38)
    distK = np.full((R, L + 1, K), INF, np.float32)
    pedge = np.full((R, L + 1, K), -1, np.int32)
    prank = np.full((R, L + 1, K), -1, np.int32)
    distK[:, 0, 0] = 0.0
    sidx = np.clip(starts, 0, L)
    for b in range(1, L + 1):
        mask = ends == b
        with np.errstate(over="ignore"):  # INF + INF -> inf is intended
            cand = np.where(mask[None, :, None],
                            distK[:, sidx, :] + costs[:, :, None], INF)
        flat = cand.reshape(R, P * K)
        sel = np.argsort(flat, axis=1, kind="stable")[:, :K]
        vals = np.take_along_axis(flat, sel, axis=1)
        ok = vals < INF
        distK[:, b, :] = np.where(ok, vals, INF)
        pedge[:, b, :] = np.where(ok, sel // K, -1)
        prank[:, b, :] = np.where(ok, sel % K, -1)
    return distK, pedge, prank


# ---------------------------------------------------------------------------
# WKV6 oracle (token-by-token recurrence)
# ---------------------------------------------------------------------------


def wkv6_ref(r, k, v, lw, u, state0):
    """Sequential RWKV6 recurrence. r,k,v,lw (B,S,H,K) f32; u (H,K);
    state0 (B,H,K,V). Returns y (B,S,H,V), final state."""
    B, S, H, K = r.shape

    def step(state, inp):
        rt, kt, vt, lwt = inp                       # (B,H,K)
        y = jnp.einsum("bhk,bhkv->bhv", rt, state) + \
            jnp.einsum("bhk,hk,bhk,bhv->bhv", rt, u, kt, vt)
        state = jnp.exp(lwt)[..., None] * state + \
            jnp.einsum("bhk,bhv->bhkv", kt, vt)
        return state, y

    xs = tuple(a.transpose(1, 0, 2, 3) for a in (r, k, v, lw))
    state, ys = jax.lax.scan(step, state0, xs)
    return ys.transpose(1, 0, 2, 3), state


# ---------------------------------------------------------------------------
# Mamba2 SSD oracle (token-by-token recurrence)
# ---------------------------------------------------------------------------


def ssd_ref(dx, la, Bm, Cm, h0):
    """dx = dt·x (B,S,H,P); la (B,S,H); Bm,Cm (B,S,G,N), head h reading
    group h // (H/G); h0 (B,H,N,P)."""
    H, G = dx.shape[2], Bm.shape[2]
    Bm, Cm = (jnp.repeat(m, H // G, axis=2) for m in (Bm, Cm))

    def step(h, inp):
        xt, lat, Bt, Ct = inp
        h = jnp.exp(lat)[..., None, None] * h + \
            jnp.einsum("bhn,bhp->bhnp", Bt, xt)
        y = jnp.einsum("bhn,bhnp->bhp", Ct, h)
        return h, y

    xs = (dx.transpose(1, 0, 2, 3), la.transpose(1, 0, 2),
          Bm.transpose(1, 0, 2, 3), Cm.transpose(1, 0, 2, 3))
    h, ys = jax.lax.scan(step, h0, xs)
    return ys.transpose(1, 0, 2, 3), h
