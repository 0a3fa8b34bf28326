"""SmolLM-135M. [hf:HuggingFaceTB/SmolLM-135M]

Llama-arch small dense decoder: 30L, d_model=576, 9 heads (GQA kv=3),
d_ff=1536, vocab=49152. Init and norm numerics from its config.json:
every Linear and Embedding drawn from N(0, 0.02**2) (`initializer_range`),
RMSNorm epsilon 1e-5 (`rms_norm_eps`).
"""
from repro.configs.base import ModelConfig, DENSE

CONFIG = ModelConfig(
    name="smollm-135m",
    family=DENSE,
    num_layers=30,
    d_model=576,
    num_heads=9,
    num_kv_heads=3,
    d_ff=1536,
    vocab_size=49152,
    max_context=2048,
    tie_embeddings=True,
    initializer_range=0.02,
    rms_norm_eps=1e-5,
    citation="hf:HuggingFaceTB/SmolLM-135M",
)
