"""moonlight-16b [moe]: the DeepSeek-V3 block — multi-head latent attention
(MLA, no q-LoRA) and 64 sigmoid-routed experts plus shared experts.

27L d_model=2048 16H (MLA: kv_lora_rank=512, qk 128 nope + 64 rope, v 128)
vocab=163840; layer 0 a dense SwiGLU of 11264, layers 1-26 MoE with 64
routed experts of 1408 (top-6, sigmoid scores, a per-expert correction
bias for choosing only, weights renormalized x 2.446) and 2 shared experts
— hf:moonshotai/Moonlight-16B-A3B (model_type deepseek_v3).
"""

from repro.configs.base import ModelConfig

FULL = ModelConfig(
    name="moonlight-16b", family="moe",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=11264, vocab_size=163840,
    num_experts=64, top_k=6, moe_d_ff=1408, n_shared_experts=2,
    first_k_dense=1, router_scoring="sigmoid", router_bias=True,
    routed_scaling=2.446,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=50000.0, norm_eps=1e-5, max_seq_len=8192,
)

SMOKE = ModelConfig(
    name="moonlight-smoke", family="moe",
    num_layers=3, d_model=128, num_heads=4, num_kv_heads=4,
    d_ff=256, vocab_size=512,
    num_experts=8, top_k=2, moe_d_ff=128, n_shared_experts=1,
    first_k_dense=1, router_scoring="sigmoid", router_bias=True,
    routed_scaling=2.446,
    kv_lora_rank=128, qk_nope_head_dim=32, qk_rope_head_dim=64,
    v_head_dim=32,
    rope_theta=50000.0, norm_eps=1e-5, max_seq_len=128,
)
