"""Zamba2 configuration file -> the served program's ``ModelConfig``."""
from __future__ import annotations

#: published settings the served block implements, and no other value
BLOCK = {"hidden_act": "gelu", "use_mem_rope": True, "use_conv_bias": True,
         "add_bias_linear": False, "use_shared_mlp_adapter": True,
         "use_shared_attention_adapter": False, "use_long_context": False,
         "time_step_limit": None}


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig

    m, serving = cfg["model"], cfg["serving"]
    for key, want in BLOCK.items():
        if m.get(key) != want:
            raise ValueError(f"the served Zamba2 block needs {key} = {want!r}, "
                             f"not {m.get(key)!r}")
    d, L = int(m["hidden_size"]), int(m["n_layer"])
    ids = tuple(int(i) for i in m["hybrid_layer_ids"])
    kinds = m["layers_block_type"]
    if (L != int(m["num_hidden_layers"]) or len(kinds) != L
            or ids != tuple(i for i, k in enumerate(kinds) if k == "hybrid")):
        raise ValueError("n_layer, num_hidden_layers, layers_block_type and "
                         "hybrid_layer_ids disagree")
    if int(m["attention_hidden_size"]) != 2 * d:
        raise ValueError("the shared blocks read [x ; x0], 2 * hidden_size")
    cfg_out = ModelConfig(
        name=cfg["name"], family="hybrid", num_layers=L, d_model=d,
        num_heads=int(m["num_attention_heads"]),
        num_kv_heads=int(m["num_key_value_heads"]),
        head_dim=int(m["attention_head_dim"]),
        d_ff=int(m["intermediate_size"]), vocab_size=int(m["vocab_size"]),
        ssm_state=int(m["mamba_d_state"]), ssm_expand=int(m["mamba_expand"]),
        ssm_head_dim=int(m["mamba_headdim"]),
        ssm_conv_width=int(m["mamba_d_conv"]),
        ssm_n_groups=int(m["mamba_ngroups"]), ssm_chunk=int(m["chunk_size"]),
        hybrid_layer_ids=ids, num_mem_blocks=int(m["num_mem_blocks"]),
        adapter_rank=int(m["adapter_rank"]),
        max_position=int(m["max_position_embeddings"]), pos_type="rope",
        rope_theta=float(m["rope_theta"]), norm_type="rmsnorm",
        norm_eps=float(m["rms_norm_eps"]), act="gelu_gated",
        tie_embeddings=True, param_dtype=serving["param_dtype"],
        activation_dtype=serving["activation_dtype"], remat=False)
    if cfg_out.ssm_expand * d // cfg_out.ssm_head_dim != int(m["n_mamba_heads"]):
        raise ValueError("n_mamba_heads disagrees with the widths")
    return cfg_out
