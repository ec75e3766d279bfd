"""Moonlight-16B-A3B [moe] — DeepSeek-V3 block: latent attention (MLA),
one leading dense layer, then 26 expert layers of 64 routed experts
(top-6, sigmoid scores with a selection-only bias) and 2 shared ones.

27L d_model=2048 16H; q_proj plain (q_lora_rank null), kv_lora_rank=512,
qk_nope/rope 128/64, v_head 128; dense d_ff=11264, expert d_ff=1408;
vocab=163840, untied head
[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3]

Served as one chip's share of an 8-chip expert-parallel deployment: the
chip holds experts 0-7 of every expert layer and routes over all 64.
"""
from repro.configs.base import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b", family="moe", layers=27, d_model=2048,
        n_heads=16, kv_heads=16, d_ff=1408, vocab=163840,
        rope_theta=50000.0, norm_eps=1e-5,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128,
        n_experts=64, top_k=6, n_shared_experts=2,
        router_scoring="sigmoid", norm_topk_prob=True, routed_scaling=2.446,
        experts_held=8, expert_offset=0,
        first_k_dense=1, dense_d_ff=11264,
        tie_embeddings=False, max_seq=8192,
    )
