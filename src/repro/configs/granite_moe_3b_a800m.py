"""Granite-3.0 MoE 3B-A800M [hf:ibm-granite/granite-3.0-3b-a800m-base]:
40 routed experts top-8 (dropless), d_expert=512, and Granite's scaled
embedding, attention, residual and logit paths."""
from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="granite-moe-3b-a800m", family="moe",
    n_layers=32, d_model=1536, n_heads=24, n_kv_heads=8,
    d_ff=512, vocab_size=49155,
    block_pattern=("moe",), mlp_type="swiglu",
    moe=MoEConfig(n_experts=40, top_k=8, d_expert=512, n_shared=0),
    rope_theta=10_000.0, norm_eps=1e-6, tie_embeddings=True,
    embedding_multiplier=12.0, attention_multiplier=0.015625,
    residual_multiplier=0.22, logits_scaling=6.0,
)

SMOKE = ModelConfig(
    name="granite-moe-3b-a800m-smoke", family="moe",
    n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
    d_ff=128, vocab_size=512,
    block_pattern=("moe",), mlp_type="swiglu",
    moe=MoEConfig(n_experts=8, top_k=2, d_expert=64, n_shared=0),
    embedding_multiplier=12.0, attention_multiplier=1 / 32,
    residual_multiplier=0.22, logits_scaling=6.0,
)
