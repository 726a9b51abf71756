"""Moonlight-16B-A3B [hf:moonshotai/Moonlight-16B-A3B] (DeepSeek-V3's
block): multi-head latent attention, one leading dense layer, then MoE
layers of 64 routed experts (top 6 by sigmoid scores with a
load-balancing bias, gates renormalised and x 2.446) and 2 shared."""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, n_dense_layers=1, d_model=2048, n_heads=16,
    n_kv_heads=16, d_ff=11264, vocab_size=163840,
    block_pattern=("moe",), mlp_type="swiglu",
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
                  score_func="sigmoid",
                  routed_scaling=2.446, bias_rate=0.001,
                  aux_weight=0.0, router_z_weight=0.0,
                  expert_pad_multiple=1),
    rope_theta=50_000.0, norm_eps=1e-5, tie_embeddings=False,
    embedding_multiplier=1.0, head_pad_multiple=1,
)

SMOKE = ModelConfig(
    name="moonlight-16b-a3b-smoke", family="moe",
    n_layers=3, n_dense_layers=1, d_model=128, n_heads=4,
    n_kv_heads=4, d_ff=256, vocab_size=512,
    block_pattern=("moe",), mlp_type="swiglu",
    mla=MLAConfig(kv_lora_rank=64, qk_nope_head_dim=32,
                  qk_rope_head_dim=16, v_head_dim=32),
    moe=MoEConfig(n_experts=16, top_k=4, d_expert=64, n_shared=2,
                  score_func="sigmoid",
                  routed_scaling=2.446, bias_rate=0.001,
                  aux_weight=0.0, router_z_weight=0.0,
                  expert_pad_multiple=1),
    rope_theta=50_000.0, norm_eps=1e-5, tie_embeddings=False,
    embedding_multiplier=1.0, head_pad_multiple=1,
)
