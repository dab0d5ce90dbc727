"""Mamba2 mixer (SSD — state-space duality), chunked + recurrent forms.

Per-head recurrence (head dim P = ssm_head_dim, state dim N = ssm_state;
B and C come in G = ssm_n_groups groups, and head h reads group
h // (H/G)):

    h_t = a_t h_{t-1} + dt_t * (B_t ⊗ x_t)        h: (N, P)
    y_t = C_t · h_t + D ⊙ x_t

with scalar-per-head decay ``a_t = exp(-exp(A_log) * dt_t)``. The chunked
form computes the intra-chunk part with a (C, C) per-head decay matrix
(all exponents non-positive → overflow-safe) and carries state across chunks
with ``lax.scan`` — the SSD algorithm restructured for the MXU: the inner
contraction ``(L ⊙ C·Bᵀ) @ (dt·x)`` is a dense matmul chain. On a TPU the
scan is the Pallas kernel ``kernels/ssd_chunk``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.kernels.ssd_chunk import ssd_chunked as ssd_pallas
from repro.models.common import Params, dense_init, pdtype, split_keys

#: the published initial range of dt (Mamba2: time_step_min/max/floor)
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4
#: the published initial range of -A = exp(A_log)
A_MIN, A_MAX = 1.0, 16.0
#: the weights the mixer consumes at the activation dtype; A_log, D,
#: dt_bias, the gated norm's weight and the conv bias stay as stored
MATMUL_WEIGHTS = ("in_proj", "out_proj", "conv_w")


def dims(cfg: ModelConfig):
    d_in = cfg.ssm_expand * cfg.d_model
    P = cfg.ssm_head_dim
    H = d_in // P
    N = cfg.ssm_state
    return d_in, H, P, N


def conv_channels(cfg: ModelConfig) -> int:
    """The xBC channels the depthwise conv runs over: d_inner + 2·G·N."""
    return cfg.ssm_expand * cfg.d_model + 2 * cfg.ssm_n_groups * cfg.ssm_state


def init_mamba2(key, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    d_in, H, P, N = dims(cfg)
    G = cfg.ssm_n_groups
    ks = split_keys(key, ["in", "out", "conv", "a", "dt"])
    pd = pdtype(cfg)
    # dt log-uniform in [DT_MIN, DT_MAX], held at DT_FLOOR, and the bias
    # that softplus maps to it; -A uniform in [A_MIN, A_MAX]
    u = jax.random.uniform(ks["dt"], (H,))
    dt = jnp.maximum(jnp.exp(u * (math.log(DT_MAX) - math.log(DT_MIN))
                             + math.log(DT_MIN)), DT_FLOOR)
    return {
        "in_proj": dense_init(ks["in"], (d, 2 * d_in + 2 * G * N + H),
                              dtype=pd),
        "conv_w": dense_init(ks["conv"], (cfg.ssm_conv_width,
                                          conv_channels(cfg)),
                             scale=0.1, dtype=pd),
        "conv_b": jnp.zeros((conv_channels(cfg),), pd),
        "A_log": jnp.log(jax.random.uniform(ks["a"], (H,), minval=A_MIN,
                                            maxval=A_MAX)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "gn_w": jnp.ones((d_in,), pd),               # gated RMSNorm
        "out_proj": dense_init(ks["out"], (d_in, d), dtype=pd),
    }


def _split_proj(cfg, proj):
    d_in = dims(cfg)[0]
    ch = conv_channels(cfg)
    return proj[..., :d_in], proj[..., d_in:d_in + ch], proj[..., d_in + ch:]


def _split_xbc(cfg, xbc):
    """xBC (..., d_in + 2GN) f32 -> x (..., H, P), B and C (..., G, N)."""
    d_in, H, P, N = dims(cfg)
    G = cfg.ssm_n_groups
    lead = xbc.shape[:-1]
    return (xbc[..., :d_in].reshape(*lead, H, P),
            xbc[..., d_in:d_in + G * N].reshape(*lead, G, N),
            xbc[..., d_in + G * N:].reshape(*lead, G, N))


def causal_conv(xBC, w, b):
    """Depthwise causal conv. xBC (B,S,Ch); w (W,Ch)."""
    W = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xBC.shape[1], :] * w[i][None, None, :]
              for i in range(W))
    return out + b[None, None, :]


def conv_step(x_new, conv_state, w, b):
    """x_new (B,Ch); conv_state (B,W-1,Ch) past inputs."""
    full = jnp.concatenate([conv_state, x_new[:, None, :]], axis=1)  # (B,W,Ch)
    out = jnp.einsum("bwc,wc->bc", full, w) + b[None, :]
    return out, full[:, 1:, :]


def _per_head(m, H):
    """(..., G, N) -> (..., H, N): head h reads group h // (H/G)."""
    return jnp.repeat(m, H // m.shape[-2], axis=-2)


def ssd_chunked(x, dt, la, Bm, Cm, h0, chunk: int):
    """Chunked SSD scan in jnp.

    x (B,S,H,P) f32; dt (B,S,H); la (B,S,H) log-decay (<=0);
    Bm, Cm (B,S,G,N); h0 (B,H,N,P). S is a multiple of ``chunk``.
    Returns y (B,S,H,P), h_final.
    """
    Bz, S, H, P = x.shape
    assert S % chunk == 0, (S, chunk)
    n = S // chunk
    r = lambda a: a.reshape(Bz, n, chunk, *a.shape[2:]).swapaxes(0, 1)
    Bh, Ch = _per_head(Bm, H), _per_head(Cm, H)
    xs, dts, las, Bs, Cs = r(x), r(dt), r(la), r(Bh), r(Ch)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))  # s <= t

    def body(h, inp):
        xc, dtc, lac, Bc, Cc = inp
        cum = jnp.cumsum(lac, axis=1)                       # (B,C,H) inclusive
        # decay matrix L[t,s] = exp(cum_t - cum_s) for s<=t  (exponent <= 0)
        diff = cum[:, :, None, :] - cum[:, None, :, :]      # (B,C,C,H)
        L = jnp.where(tri[None, :, :, None], jnp.exp(diff), 0.0)
        G = jnp.einsum("bthn,bshn->btsh", Cc, Bc)           # (B,C,C,H)
        dx = xc * dtc[..., None]                            # (B,C,H,P)
        y = jnp.einsum("btsh,bshp->bthp", G * L, dx)
        # inter-chunk: y_t += C_t . (exp(cum_t) * h0)
        dec = jnp.exp(cum)                                  # (B,C,H)
        y = y + jnp.einsum("bthn,bhnp,bth->bthp", Cc, h, dec)
        # state: h' = exp(cum_last)*h + sum_s exp(cum_last-cum_s) dt_s B_s x_s
        rdec = jnp.exp(cum[:, -1:, :] - cum)                # (B,C,H)
        h_new = dec[:, -1][:, :, None, None] * h + \
            jnp.einsum("bshn,bshp,bsh->bhnp", Bc, dx, rdec)
        return h_new, y

    h, ys = jax.lax.scan(body, h0, (xs, dts, las, Bs, Cs))
    y = ys.swapaxes(0, 1).reshape(Bz, S, H, P)
    return y, h


def ssd(x, dt, la, Bm, Cm, h0, chunk: int):
    """The chunked scan over any length: padded to a multiple of
    ``chunk`` with steps that leave the state as it is (dt 0, decay 1),
    by the Pallas kernel on a TPU and ``ssd_chunked`` elsewhere."""
    S = x.shape[1]
    pad = -S % chunk
    if pad:
        wide = lambda a: jnp.pad(a, [(0, 0), (0, pad)]
                                 + [(0, 0)] * (a.ndim - 2))
        x, dt, la, Bm, Cm = map(wide, (x, dt, la, Bm, Cm))
    if ops.on_tpu():
        y, h = ssd_pallas(x * dt[..., None], la, Bm, Cm, h0, chunk=chunk)
    else:
        y, h = ssd_chunked(x, dt, la, Bm, Cm, h0, chunk)
    return y[:, :S], h


def ssd_step(x, dt, la, Bm, Cm, h):
    """One token. x (B,H,P); dt,la (B,H); Bm,Cm (B,G,N); h (B,H,N,P)."""
    H = x.shape[1]
    Bh, Ch = _per_head(Bm, H), _per_head(Cm, H)
    a = jnp.exp(la)[..., None, None]
    h = a * h + jnp.einsum("bhn,bhp,bh->bhnp", Bh, x, dt)
    y = jnp.einsum("bhn,bhnp->bhp", Ch, h)
    return y, h


def _gated_rmsnorm(y, z, w, groups: int, eps=1e-5):
    """RMS(y · silu(z)) over ``groups`` equal groups of channels."""
    yf = (y * jax.nn.silu(z)).astype(jnp.float32)
    g = yf.reshape(*yf.shape[:-1], groups, -1)
    var = jnp.mean(g * g, -1, keepdims=True)
    g = g * jax.lax.rsqrt(var + eps)
    return g.reshape(yf.shape) * w.astype(jnp.float32)


def _decay(cfg, p, dt_raw):
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    return dt, -jnp.exp(p["A_log"]) * dt


def mamba2_forward(cfg: ModelConfig, p: Params, x, state=None):
    """Full-sequence mixer. x (B,S,d) -> (B,S,d), (conv_state, ssm_state)."""
    from repro.distributed.sharding import constrain
    Bz, S, d = x.shape
    d_in, H, P, N = dims(cfg)
    dt_a = x.dtype
    proj = constrain(x @ p["in_proj"].astype(dt_a), "batch", "seq", "ff")
    z, xBC, dt = _split_proj(cfg, proj)
    if state is not None:
        conv_state = state[0]
        # prepend cached conv inputs (only used in segment-continuation mode)
        xBC_in = jnp.concatenate([conv_state, xBC], axis=1)
        xBC_conv = causal_conv(xBC_in, p["conv_w"].astype(dt_a),
                               p["conv_b"].astype(dt_a))[:, conv_state.shape[1]:]
        h0 = state[1]
    else:
        xBC_conv = causal_conv(xBC, p["conv_w"].astype(dt_a),
                               p["conv_b"].astype(dt_a))
        h0 = jnp.zeros((Bz, H, N, P), jnp.float32)
    xs, Bm, Cm = _split_xbc(cfg, jax.nn.silu(xBC_conv).astype(jnp.float32))
    xs = constrain(xs, "batch", "seq", "heads", None)
    dt, la = _decay(cfg, p, dt)                              # (B,S,H)
    y, h = ssd(xs, dt, la, Bm, Cm, h0, chunk=cfg.ssm_chunk)
    y = y + xs * p["D"][None, None, :, None]
    y = constrain(y.reshape(Bz, S, d_in), "batch", "seq", "ff")
    y = _gated_rmsnorm(y, z.astype(jnp.float32), p["gn_w"], cfg.ssm_n_groups)
    out = constrain(y.astype(dt_a) @ p["out_proj"].astype(dt_a),
                    "batch", "seq", "embed")
    W1 = cfg.ssm_conv_width - 1
    if S >= W1:
        new_conv = xBC[:, -W1:, :]
    else:
        new_conv = jnp.pad(xBC, ((0, 0), (W1 - S, 0), (0, 0)))
    return out, (new_conv, h)


def mamba2_step(cfg: ModelConfig, p: Params, x, state):
    """One-token mixer. x (B,1,d); state = (conv (B,W-1,Ch), ssm (B,H,N,P))."""
    Bz, _, d = x.shape
    d_in = dims(cfg)[0]
    dt_a = x.dtype
    conv_state, h = state
    proj = (x[:, 0] @ p["in_proj"].astype(dt_a))
    z, xBC, dt = _split_proj(cfg, proj)
    xBC_c, conv_state = conv_step(xBC, conv_state, p["conv_w"].astype(dt_a),
                                  p["conv_b"].astype(dt_a))
    xs, Bm, Cm = _split_xbc(cfg, jax.nn.silu(xBC_c).astype(jnp.float32))
    dt, la = _decay(cfg, p, dt)
    y, h = ssd_step(xs, dt, la, Bm, Cm, h)
    y = y + xs * p["D"][None, :, None]
    y = y.reshape(Bz, d_in)
    y = _gated_rmsnorm(y, z.astype(jnp.float32), p["gn_w"], cfg.ssm_n_groups)
    out = (y.astype(dt_a) @ p["out_proj"].astype(dt_a))[:, None, :]
    return out, (conv_state, h)
