"""SmolLM-360M — llama-architecture small model
[hf:HuggingFaceTB/SmolLM-360M, config.json].

Published widths: 32 layers, hidden 960, 15 query heads and 5 KV heads
of 64, intermediate 2560, vocab 49152, tied embeddings, RoPE theta
10000.  FULL keeps them all; SMOKE is a 2-layer, d_model 96 f32
stand-in for CPU tests.
"""

from repro.models.config import ModelConfig

FULL = ModelConfig(
    name="smollm-360m",
    n_layers=32, d_model=960, n_heads=15, n_kv_heads=5, d_head=64,
    d_ff=2560, vocab_size=49152, tie_embeddings=True,
    rope_theta=10000.0,
)

SMOKE = ModelConfig(
    name="smollm-smoke",
    n_layers=2, d_model=96, n_heads=6, n_kv_heads=2, d_head=16,
    d_ff=192, vocab_size=512, tie_embeddings=True,
    compute_dtype="float32", cache_dtype="float32",
)
