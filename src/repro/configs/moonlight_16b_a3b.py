"""moonlight-16b-a3b — DeepSeek-V3 block: MLA (q_lora_rank null, latent
512, nope 128 / rope 64 / v 128 per head), one leading dense layer, then
64 routed experts (width 1408, top-6, sigmoid scores, noaux_tc selection
bias, renormalised, x2.446) beside 2 shared experts
[hf:moonshotai/Moonlight-16B-A3B config.json]. The router's balancing
term is not in the loss (noaux_tc balances by the bias)."""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=11264, vocab_size=163840,
    num_experts=64, experts_per_token=6, experts_held=64,
    moe_d_ff=1408, num_shared_experts=2, first_dense_layers=1,
    routed_scaling=2.446, router_aux_weight=0.0,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    activation="silu", rope_theta=5e4, norm="rmsnorm", norm_eps=1e-5,
    tie_embeddings=False,
    source="Moonlight-16B-A3B "
           "[hf:moonshotai/Moonlight-16B-A3B/blob/main/config.json]",
)
