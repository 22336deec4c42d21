"""granite-moe-3b-a800m [moe] — 40 experts, top-8 routing, tied embeddings
and Granite's four multipliers
[hf:ibm-granite/granite-3.0-3b-a800m-base]."""

from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="granite-moe-3b-a800m",
    family="moe",
    n_layers=32,
    d_model=1536,
    n_heads=24,
    n_kv_heads=8,       # GQA kv=8
    head_dim=64,
    d_ff=512,           # per expert
    vocab_size=49155,
    n_experts=40,
    experts_per_token=8,
    activation="swiglu",
    tie_embeddings=True,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.015625,   # 1/64, not 1/sqrt(64)
    logits_scaling=6.0,
    citation="hf:ibm-granite/granite-3.0-3b-a800m-base",
)
