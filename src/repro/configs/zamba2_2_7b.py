"""Zamba2-2.7B — Mamba2 backbone with two shared attention+MLP blocks.
[arXiv:2411.15242; hf]

Assumed (not confirmed from the published ``config.json``, which is not
in this repository): the hybrid layers, every sixth from layer 5 (nine
of 54); ``ssm_n_groups`` 1; ``adapter_rank`` 128 and ``num_mem_blocks``
2, as in Zamba2-7B; the shared blocks' head dim 160, the family's rule
2 * hidden / heads.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="zamba2-2.7b",
    family="hybrid",
    num_layers=54,          # mamba2 blocks
    d_model=2_560,
    num_heads=32,           # shared attention block heads
    num_kv_heads=32,
    head_dim=160,           # heads read [x ; x0], 2 * d_model wide
    d_ff=10_240,            # shared block MLP
    vocab_size=32_000,
    ssm_state=64,
    ssm_expand=2,
    ssm_head_dim=64,
    ssm_conv_width=4,
    ssm_n_groups=1,
    hybrid_layer_ids=tuple(range(5, 54, 6)),
    num_mem_blocks=2,
    adapter_rank=128,
    pos_type="rope",
    rope_theta=10_000.0,
    norm_type="rmsnorm",
    act="gelu_gated",
    tie_embeddings=True,
)
