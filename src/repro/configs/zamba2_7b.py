"""Zamba2-7B(-Instruct) — 81 Mamba2 layers, 13 of them hybrid: each first
runs one of two shared attention+MLP blocks, in turn, on the hidden state
concatenated with the original token embeddings.
[https://huggingface.co/Zyphra/Zamba2-7B-Instruct/blob/main/config.json]

Assumed where that config is silent: ``tie_word_embeddings`` (the head is
the embedding table) and the gated norm's group size d_inner / n_groups.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="zamba2-7b",
    family="hybrid",
    num_layers=81,
    d_model=3_584,
    num_heads=32,
    num_kv_heads=32,
    head_dim=224,           # attention_head_dim: 7168 / 32
    d_ff=14_336,
    vocab_size=32_000,
    ssm_state=64,
    ssm_expand=2,
    ssm_head_dim=64,
    ssm_conv_width=4,
    ssm_n_groups=2,
    ssm_chunk=256,
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    num_mem_blocks=2,
    adapter_rank=128,
    max_position=4_096,
    pos_type="rope",
    rope_theta=10_000.0,
    norm_type="rmsnorm",
    norm_eps=1e-5,
    act="gelu_gated",
    tie_embeddings=True,
)
