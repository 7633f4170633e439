"""Moonlight-16B-A3B [hf:moonshotai/Moonlight-16B-A3B, config.json;
model_type deepseek_v3, layer equations of arXiv:2412.19437 Sec. 2.1].

27 layers (the first dense, SwiGLU 11264; then 26 MoE), hidden 2048,
RMSNorm eps 1e-5, untied embeddings over a 163,840 vocabulary.  MLA with
16 heads and no query low-rank: 128 nope + 64 RoPE dims of query and key
per head, a 512-wide KV latent, 128-wide values, RoPE theta 50,000.
MoE: 64 routed experts of width 1408, 6 per token by sigmoid score plus
correction bias, normalised and scaled by 2.446, and 2 shared experts.

The HSFL round trains one chip's share of it (``ModelConfig.cut``): a
chip of an 8-way expert- and vocab-parallel deployment holds 8 of the 64
experts of each layer and an eighth of the vocabulary; the layers kept
are the dense one and the first MoE layers (the rest would sit on further
pipeline stages).  Every width stays as published.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    citation="hf:moonshotai/Moonlight-16B-A3B",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=128,
    d_ff=11264,
    vocab_size=163840,
    rope_theta=50_000.0,
    num_experts=64,
    experts_per_token=6,
    moe_d_ff=1408,
    n_shared_experts=2,
    routed_scaling_factor=2.446,
    first_k_dense=1,
    kv_lora_rank=512,
    qk_rope_head_dim=64,
    v_head_dim=128,
    norm_eps=1e-5,
)

