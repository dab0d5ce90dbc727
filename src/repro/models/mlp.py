"""Feed-forward blocks: SwiGLU (llama-style) and plain GELU MLP."""
from __future__ import annotations

import jax

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.common import Params, dense_init, pdtype, split_keys


def init_mlp(key, cfg: ModelConfig, d_in=None, d_ff=None) -> Params:
    d = d_in or cfg.d_model
    f = d_ff or cfg.d_ff
    ks = split_keys(key, ["wi", "wg", "wo"])
    p = {
        "wi": dense_init(ks["wi"], (d, f), dtype=pdtype(cfg)),
        "wo": dense_init(ks["wo"], (f, d), dtype=pdtype(cfg)),
    }
    if cfg.act == "silu":  # gated
        p["wg"] = dense_init(ks["wg"], (d, f), dtype=pdtype(cfg))
    return p


#: the weights ``apply_mlp`` consumes at the activation dtype
MATMUL_WEIGHTS = ("wi", "wg", "wo")


def apply_mlp(cfg: ModelConfig, p: Params, x):
    dt = x.dtype
    h = constrain(x @ p["wi"].astype(dt), "batch", "seq", "ff")
    if cfg.act == "silu":
        h = jax.nn.silu(h) * constrain(x @ p["wg"].astype(dt),
                                       "batch", "seq", "ff")
    else:
        h = jax.nn.gelu(h, approximate=True)
    return constrain(h @ p["wo"].astype(dt), "batch", "seq", "embed")
