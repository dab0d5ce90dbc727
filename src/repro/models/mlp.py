"""Feed-forward blocks: SwiGLU (llama-style), the plain tanh-GELU MLP
(GPT-2) and the gated exact-GELU MLP (Zamba2's shared blocks)."""
from __future__ import annotations

import jax

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.common import Params, dense_init, pdtype, split_keys

#: acts with a gate: ``act(x @ wi) * (x @ wg)``
GATED = {"silu": jax.nn.silu,
         "gelu_gated": lambda h: jax.nn.gelu(h, approximate=False)}


def init_mlp(key, cfg: ModelConfig, d_in=None, d_ff=None) -> Params:
    d = d_in or cfg.d_model
    f = d_ff or cfg.d_ff
    ks = split_keys(key, ["wi", "wg", "wo"])
    p = {
        "wi": dense_init(ks["wi"], (d, f), dtype=pdtype(cfg)),
        "wo": dense_init(ks["wo"], (f, d), dtype=pdtype(cfg)),
    }
    if cfg.act in GATED:
        p["wg"] = dense_init(ks["wg"], (d, f), dtype=pdtype(cfg))
    return p


#: the weights ``apply_mlp`` consumes at the activation dtype
MATMUL_WEIGHTS = ("wi", "wg", "wo")


def apply_mlp(cfg: ModelConfig, p: Params, x, delta=None):
    """``delta``, where given, is added to the gated act's two inputs: a
    pair (to ``x @ wi``, to ``x @ wg``), such as a LoRA's output."""
    dt = x.dtype
    h = constrain(x @ p["wi"].astype(dt), "batch", "seq", "ff")
    if cfg.act in GATED:
        g = constrain(x @ p["wg"].astype(dt), "batch", "seq", "ff")
        if delta is not None:
            h, g = h + delta[0], g + delta[1]
        h = GATED[cfg.act](h) * g
    else:
        h = jax.nn.gelu(h, approximate=True)
    return constrain(h @ p["wo"].astype(dt), "batch", "seq", "embed")
