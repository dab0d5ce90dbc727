"""Plain float32 Zamba2 forward pass, the reference that decides ``correct``.

It follows the Zamba2 block as the Hugging Face ``zamba2`` config states
it (Zyphra, arXiv:2411.15242): a stack of Mamba2 layers, each
``x + Mamba2(RMS(x))``; the layers of ``hybrid_layer_ids`` first run one
of ``num_mem_blocks`` shared attention+MLP blocks, in turn, on
``RMS([x ; x0])`` with x0 the token embeddings, add their own ``linear``
of its output to the mixer's input, ``x + Mamba2(RMS(x + u))``; the
shared MLP is ``down(gelu(gate) * up)`` with a rank-``adapter_rank``
LoRA of the layer's own on ``[gate ; up]``. The Mamba2 scan is written as
the per-position recurrence, a ``lax.scan`` over positions, independent
of the program's chunked form. Departures, all shared with the served
program so the two compute the same function:

- the layers are ``n_layer`` of the published 81 (the configuration's
  depth cut);
- assumed where the config is silent: the head is tied to the embedding;
  the gated norm normalises groups of d_inner / mamba_ngroups channels;
  q.k is scaled by (attention_head_dim / 2)^-1/2; ``gelu`` is exact;
  rotary embedding over the whole head, halves rotated;
- weights are drawn from the seed with the served program's recipe
  (normal, std 0.02, conv 0.1; A_log = log U(1, 16); dt_bias the inverse
  softplus of a log-uniform dt; split from one key in a fixed order),
  regenerated here so that nothing the program made is read back. Norm
  weights are 1 and the conv bias 0 at initialisation and are not stored.

``init_params`` and ``logits`` are plain jnp, at
``jax.lax.Precision.HIGHEST``. ``rounding`` is the hook that the control
(``fp8``) uses: it rounds every matmul operand and the residual stream
to a narrower type, where the served program rounds to bfloat16.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
INIT_STD, CONV_STD = 0.02, 0.1

#: types a matmul operand and the residual stream are rounded to
ROUNDINGS = {"float32": None, "float8_e4m3fn": jnp.float8_e4m3fn}


def sizes(model: dict) -> dict:
    """The shape keys of a Hugging Face Zamba2 config."""
    d, e = int(model["hidden_size"]), int(model["mamba_expand"])
    return {"L": int(model["n_layer"]), "d": d, "Di": e * d,
            "H": e * d // int(model["mamba_headdim"]),
            "P": int(model["mamba_headdim"]), "N": int(model["mamba_d_state"]),
            "G": int(model["mamba_ngroups"]), "W": int(model["mamba_d_conv"]),
            "A": int(model["num_attention_heads"]),
            "Akv": int(model["num_key_value_heads"]),
            "hd": int(model["attention_head_dim"]),
            "F": int(model["intermediate_size"]), "V": int(model["vocab_size"]),
            "r": int(model["adapter_rank"]),
            "blocks": int(model["num_mem_blocks"]),
            "hybrid": tuple(int(i) for i in model["hybrid_layer_ids"]),
            "eps": float(model["rms_norm_eps"]),
            "theta": float(model["rope_theta"]),
            "dt_min": float(model["time_step_min"]),
            "dt_max": float(model["time_step_max"]),
            "dt_floor": float(model["time_step_floor"])}


def _normal(key, shape, std=INIT_STD):
    return (std * jax.random.normal(key, shape)).astype(jnp.float32)


def _mixer(key, s):
    k_in, k_out, k_conv, k_a, k_dt = jax.random.split(key, 5)
    ch = s["Di"] + 2 * s["G"] * s["N"]
    u = jax.random.uniform(k_dt, (s["H"],))
    lo, hi = math.log(s["dt_min"]), math.log(s["dt_max"])
    dt = jnp.maximum(jnp.exp(u * (hi - lo) + lo), s["dt_floor"])
    return {"in_proj": _normal(k_in, (s["d"], s["Di"] + ch + s["H"])),
            "out_proj": _normal(k_out, (s["Di"], s["d"])),
            "conv": _normal(k_conv, (s["W"], ch), CONV_STD),
            "A_log": jnp.log(jax.random.uniform(k_a, (s["H"],), minval=1.0,
                                                maxval=16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt))}


def _shared(key, s):
    k_attn, k_mlp = jax.random.split(key)
    kq, kk, kv, ko = jax.random.split(k_attn, 4)
    k_gate, k_up, k_down = jax.random.split(k_mlp, 3)
    d, q, kvw = s["d"], s["A"] * s["hd"], s["Akv"] * s["hd"]
    return {"wq": _normal(kq, (2 * d, q)), "wk": _normal(kk, (2 * d, kvw)),
            "wv": _normal(kv, (2 * d, kvw)), "wo": _normal(ko, (q, d)),
            "gate": _normal(k_gate, (d, s["F"])),
            "up": _normal(k_up, (d, s["F"])),
            "down": _normal(k_down, (s["F"], d))}


def _own(key, s):
    k_lin, k_a, k_b = jax.random.split(key, 3)
    return {"linear": _normal(k_lin, (s["d"], s["d"])),
            "lora_a": _normal(k_a, (s["d"], s["r"])),
            "lora_b": _normal(k_b, (s["r"], 2 * s["F"]))}


@partial(jax.jit, static_argnames=("s",))
def _init(key, s):
    s = dict(s)
    k_emb, k_mamba, k_shared, k_hybrid = jax.random.split(key, 4)
    k_tok, _, _ = jax.random.split(k_emb, 3)
    return {"tok": _normal(k_tok, (s["V"], s["d"])),
            "mixers": jax.vmap(lambda k: _mixer(k, s))(
                jax.random.split(k_mamba, s["L"])),
            "shared": jax.vmap(lambda k: _shared(k, s))(
                jax.random.split(k_shared, s["blocks"])),
            "own": jax.vmap(lambda k: _own(k, s))(
                jax.random.split(k_hybrid, len(s["hybrid"])))}


def init_params(seed: int, model: dict) -> dict:
    """Weights for ``seed``, made on the device in one jitted call."""
    return _init(jax.random.PRNGKey(seed), tuple(sorted(sizes(model).items())))


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _round(x, to):
    return x if to is None else x.astype(to).astype(jnp.float32)


def _mm(a, w, to):
    return jnp.matmul(_round(a, to), _round(w, to), precision=HIGHEST)


def _rope(x, theta):
    """x (B, T, heads, hd): rotate halves by position-dependent angles."""
    T, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv      # (T, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _shared_block(p, own, x, x0, s, to):
    """linear(T(x, x0)): attention over [x ; x0], the LoRA'd gated MLP."""
    B, T, _ = x.shape
    c = _rms(jnp.concatenate([x, x0], -1), s["eps"])
    q, k, v = (_mm(c, p[w], to).reshape(B, T, -1, s["hd"])
               for w in ("wq", "wk", "wv"))
    q, k = _rope(q, s["theta"]), _rope(k, s["theta"])
    k, v = (jnp.repeat(a, s["A"] // s["Akv"], axis=2) for a in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", _round(q, to), _round(k, to),
                        precision=HIGHEST) * (s["hd"] / 2) ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _round(probs, to), _round(v, to),
                   precision=HIGHEST).reshape(B, T, -1)
    h = _rms(_mm(o, p["wo"], to), s["eps"])
    lora = _mm(_mm(h, own["lora_a"], to), own["lora_b"], to)
    gate = _mm(h, p["gate"], to) + lora[..., :s["F"]]
    up = _mm(h, p["up"], to) + lora[..., s["F"]:]
    m = _mm(jax.nn.gelu(gate, approximate=False) * up, p["down"], to)
    return _mm(m, own["linear"], to)


def _mamba2(p, x, s, to):
    """The Mamba2 mixer over a whole sequence, by its recurrence."""
    B, T, _ = x.shape
    Di, H, P, N, G = s["Di"], s["H"], s["P"], s["N"], s["G"]
    proj = _mm(x, p["in_proj"], to)
    z, xbc, dt = (proj[..., :Di], proj[..., Di:2 * Di + 2 * G * N],
                  proj[..., 2 * Di + 2 * G * N:])
    pad = jnp.pad(xbc, ((0, 0), (s["W"] - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(pad[:, i:i + T] * p["conv"][i]
                          for i in range(s["W"])))
    xs = xbc[..., :Di].reshape(B, T, H, P)
    Bm = jnp.repeat(xbc[..., Di:Di + G * N].reshape(B, T, G, N), H // G, 2)
    Cm = jnp.repeat(xbc[..., Di + G * N:].reshape(B, T, G, N), H // G, 2)
    dt = jax.nn.softplus(dt + p["dt_bias"])                     # (B, T, H)
    a = jnp.exp(-jnp.exp(p["A_log"]) * dt)

    def step(h, inp):
        x_t, b_t, c_t, dt_t, a_t = inp
        h = (a_t[..., None, None] * h
             + dt_t[..., None, None] * b_t[..., :, None] * x_t[..., None, :])
        return h, jnp.einsum("bhn,bhnp->bhp", c_t, h, precision=HIGHEST)

    seq = tuple(jnp.moveaxis(t, 1, 0) for t in (xs, Bm, Cm, dt, a))
    _, y = jax.lax.scan(step, jnp.zeros((B, H, N, P), jnp.float32), seq)
    y = jnp.moveaxis(y, 0, 1) + xs                              # D = 1
    g = (y.reshape(B, T, Di) * jax.nn.silu(z)).reshape(B, T, G, Di // G)
    return _mm(_rms(g, s["eps"]).reshape(B, T, Di), p["out_proj"], to)


@partial(jax.jit, static_argnames=("s", "rounding"))
def _logits(params, tokens, s, rounding):
    s = dict(s)
    to = ROUNDINGS[rounding]
    x0 = params["tok"][tokens]
    x = _round(x0, to)
    for layer in range(s["L"]):
        h = x
        if layer in s["hybrid"]:
            r = s["hybrid"].index(layer)
            own = jax.tree.map(lambda a: a[r], params["own"])
            blk = jax.tree.map(lambda a: a[r % s["blocks"]], params["shared"])
            h = x + _shared_block(blk, own, x, x0, s, to)
        p = jax.tree.map(lambda a: a[layer], params["mixers"])
        x = _round(x + _mamba2(p, _rms(h, s["eps"]), s, to), to)
    return _mm(_rms(x, s["eps"]), params["tok"].T, to)


def logits(params, tokens, model: dict, rounding: str = "float32"):
    """(B, T) int tokens -> (B, T, V) float32 logits at every position."""
    return _logits(params, jnp.asarray(tokens, jnp.int32),
                   tuple(sorted(sizes(model).items())), rounding)
