"""Moonlight-16B-A3B (moonshot-v1-16b-a3b) [hf:moonshotai/Moonlight-16B-A3B],
``model_type: deepseek_v3``, as its published config.json states it.

27 layers, d_model 2048, 16 heads of latent attention (MLA: kv_lora_rank
512, qk_nope_head_dim 128, qk_rope_head_dim 64, v_head_dim 128, no query
LoRA); layer 0 a dense MLP of 11264 (first_k_dense_replace 1), then 26
expert layers of 64 routed experts of width 1408, 6 per token (sigmoid
scores, noaux_tc correction bias, norm_topk_prob, routed_scaling_factor
2.446, n_group = topk_group = 1) and 2 shared experts; vocabulary 163840,
untied; RMSNorm eps 1e-5; rope theta 50000, no scaling.
"""

from repro.configs.base import ModelConfig, register


@register("moonshot-v1-16b-a3b")
def config() -> ModelConfig:
    return ModelConfig(
        name="moonshot-v1-16b-a3b",
        family="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        d_ff=1408,
        vocab_size=163840,
        activation="swiglu",
        n_experts=64,
        experts_per_token=6,
        moe_shared_experts=2,
        moe_scoring="sigmoid",
        routed_scaling=2.446,
        first_k_dense=1,
        dense_d_ff=11264,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        pos_type="rope",
        rope_theta=50000.0,
        norm_eps=1e-5,
        max_seq_len=8192,
        source="hf:moonshotai/Moonlight-16B-A3B",
    )
