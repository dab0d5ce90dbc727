"""Plain float32 GPT-2 forward pass, the reference that decides ``correct``.

It follows Radford et al. (2019) and the Hugging Face ``gpt2`` code:
learned token and position embeddings, pre-norm blocks of causal
multi-head attention and a tanh-GELU MLP of width ``n_inner``, a final
layer norm and a head tied to the token embedding. Departures, all shared
with the served program so the two compute the same function:

- no biases on the projections: GPT-2 initialises them to zero and the
  served program has none, so they are held at zero here;
- weights are drawn from the seed with the served program's recipe
  (normal, std 0.02, split from one key in a fixed order), regenerated
  here so that nothing the program made is read back.

``init_params`` and ``logits`` are plain jnp, at
``jax.lax.Precision.HIGHEST``. ``rounding`` is the hook that the control
(``fp8``) uses: it rounds every matmul operand and the residual stream
to a narrower type, where the served program rounds to bfloat16.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
INIT_STD = 0.02

#: types a matmul operand and the residual stream are rounded to
ROUNDINGS = {"float32": None, "float8_e4m3fn": jnp.float8_e4m3fn}


def sizes(model: dict) -> dict:
    """The shape keys of a Hugging Face GPT-2 config, with defaults."""
    d = int(model["n_embd"])
    return {"L": int(model["n_layer"]), "d": d, "H": int(model["n_head"]),
            "F": int(model.get("n_inner") or 4 * d),
            "V": int(model["vocab_size"]), "T": int(model["n_positions"]),
            "eps": float(model.get("layer_norm_epsilon", 1e-5))}


def _normal(key, shape):
    return (INIT_STD * jax.random.normal(key, shape)).astype(jnp.float32)


def _block(key, s):
    d, F = s["d"], s["F"]
    k_attn, k_ffn, _, _ = jax.random.split(key, 4)
    kq, kk, kv, ko = jax.random.split(k_attn, 4)
    k_in, _, k_out = jax.random.split(k_ffn, 3)
    return {"wq": _normal(kq, (d, d)), "wk": _normal(kk, (d, d)),
            "wv": _normal(kv, (d, d)), "wo": _normal(ko, (d, d)),
            "w_in": _normal(k_in, (d, F)), "w_out": _normal(k_out, (F, d))}


@partial(jax.jit, static_argnames=("s",))
def _init(key, s):
    s = dict(s)
    k_emb, k_layers, _ = jax.random.split(key, 3)
    k_tok, k_pos, _ = jax.random.split(k_emb, 3)
    blocks = jax.vmap(lambda k: _block(k, s))(
        jax.random.split(k_layers, s["L"]))
    return {"tok": _normal(k_tok, (s["V"], s["d"])),
            "pos": _normal(k_pos, (s["T"], s["d"])), "blocks": blocks}


def init_params(seed: int, model: dict) -> dict:
    """Weights for ``seed``, made on the device in one jitted call. Layer
    norms are the identity at initialisation (scale 1, shift 0) and are
    not stored."""
    return _init(jax.random.PRNGKey(seed), tuple(sorted(sizes(model).items())))


def _layernorm(x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _round(x, to):
    return x if to is None else x.astype(to).astype(jnp.float32)


def _mm(a, w, to):
    return jnp.matmul(_round(a, to), _round(w, to), precision=HIGHEST)


@partial(jax.jit, static_argnames=("s", "rounding"))
def _logits(params, tokens, s, rounding):
    s = dict(s)
    to = ROUNDINGS[rounding]
    B, T = tokens.shape
    H, d = s["H"], s["d"]
    hd = d // H
    x = _round(params["tok"][tokens] + params["pos"][:T][None], to)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def block(x, p):
        h = _layernorm(x, s["eps"])
        q, k, v = (_mm(h, p[w], to).reshape(B, T, H, hd)
                   for w in ("wq", "wk", "wv"))
        scores = jnp.einsum("bqhd,bkhd->bhqk", _round(q, to), _round(k, to),
                            precision=HIGHEST) / jnp.sqrt(jnp.float32(hd))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        o = jnp.einsum("bhqk,bkhd->bqhd", _round(probs, to), _round(v, to),
                       precision=HIGHEST).reshape(B, T, d)
        x = _round(x + _mm(o, p["wo"], to), to)
        h = _layernorm(x, s["eps"])
        y = _mm(jax.nn.gelu(_mm(h, p["w_in"], to), approximate=True),
                p["w_out"], to)
        return _round(x + y, to), None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    h = _layernorm(x, s["eps"])
    return _mm(h, params["tok"].T, to)


def logits(params, tokens, model: dict, rounding: str = "float32"):
    """(B, T) int tokens -> (B, T, V) float32 logits at every position."""
    return _logits(params, jnp.asarray(tokens, jnp.int32),
                   tuple(sorted(sizes(model).items())), rounding)
