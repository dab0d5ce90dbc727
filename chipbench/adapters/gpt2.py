"""GPT-2 configuration file -> the served program's ``ModelConfig``."""
from __future__ import annotations


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig

    m, serving = cfg["model"], cfg["serving"]
    if m.get("activation_function") != "gelu_new":
        raise ValueError("the served GPT-2 block uses the tanh GELU "
                         "(gelu_new)")
    d, h = int(m["n_embd"]), int(m["n_head"])
    return ModelConfig(
        name=cfg["name"], family="dense", num_layers=int(m["n_layer"]),
        d_model=d, num_heads=h, num_kv_heads=h, head_dim=d // h,
        d_ff=int(m.get("n_inner") or 4 * d), vocab_size=int(m["vocab_size"]),
        pos_type="learned", max_position=int(m["n_positions"]),
        norm_type="layernorm", act="gelu",
        tie_embeddings=bool(m.get("tie_word_embeddings", True)),
        norm_eps=float(m.get("layer_norm_epsilon", 1e-5)),
        param_dtype=serving["param_dtype"],
        activation_dtype=serving["activation_dtype"], remat=False)
