"""deepseek-v2-lite [moe]: 27L d2048 16H MLA (no query LoRA, kv_lora 512,
nope 128 + rope 64, v 128), layer 0 dense SwiGLU d_ff=10944, layers 1-26
MoE: 64 routed experts of d_ff=1408, top-6 softmax router without
renormalisation, 2 shared experts; YaRN rope (factor 40 over 4096
positions); vocab=102400, untied.
[hf:deepseek-ai/DeepSeek-V2-Lite, arXiv:2405.04434]"""
from repro.configs.base import ModelConfig

ARCH_ID = "deepseek-v2-lite"

_MLA_MOE = dict(
    layer_pattern=("mla+moe",), first_dense_layers=1, q_lora_rank=0,
    norm_topk_prob=False, rope_interleaved=True, rope_scaling_factor=40.0,
    rope_original_max_position=4096, rope_beta_fast=32.0,
    rope_beta_slow=1.0, rope_mscale=0.707, rope_mscale_all_dim=0.707)


def full_config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID, family="moe", num_layers=27, d_model=2048,
        num_heads=16, num_kv_heads=16, d_ff=10944, vocab_size=102400,
        num_experts=64, experts_per_token=6, moe_d_ff=1408,
        n_shared_experts=2, kv_lora_rank=512, qk_nope_dim=128,
        qk_rope_dim=64, v_head_dim=128, norm_eps=1e-6, **_MLA_MOE)


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID + "-smoke", family="moe", num_layers=3, d_model=64,
        num_heads=4, num_kv_heads=4, d_ff=96, vocab_size=256,
        num_experts=8, experts_per_token=3, moe_d_ff=32,
        n_shared_experts=2, kv_lora_rank=32, qk_nope_dim=16,
        qk_rope_dim=8, v_head_dim=16, norm_eps=1e-6, dtype="float32",
        **_MLA_MOE)
