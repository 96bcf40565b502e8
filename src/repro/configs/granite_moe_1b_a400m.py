"""granite-moe-1b-a400m [moe] — 32 experts top-8. [hf:ibm-granite/granite-3.0-1b-a400m-base]

24L d_model=1024 16H (GQA kv=8), per-expert d_ff=512, vocab=49155.
"""
from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="granite-moe-1b-a400m", family="moe",
    num_layers=24, d_model=1024, num_heads=16, num_kv_heads=8,
    d_ff=512, vocab_size=49155,
    moe=MoEConfig(num_experts=32, top_k=8, d_ff=512),
    tie_embeddings=True,
)

SMOKE_CONFIG = CONFIG.replace(
    num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, d_ff=32,
    vocab_size=128,
    moe=MoEConfig(num_experts=4, top_k=2, d_ff=32),
)
