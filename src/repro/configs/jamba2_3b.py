"""AI21 Jamba2-3B [hf:ai21labs/AI21-Jamba2-3B].

Hybrid Mamba-1 + attention: 28 layers as two periods of 14, each with 13
Mamba-1 mixers and one attention layer at slot 7 (``attn_layer_offset``).
Attention is 20 query heads over one KV head, no positional encoding; every
layer has a dense SwiGLU MLP (``num_experts`` 1).  The Mamba mixers pass the
dt slice, B and C through RMSNorms.  Tied embedding.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="jamba2-3b",
    family="hybrid",
    n_layers=28,
    d_model=2560,
    n_heads=20,
    n_kv_heads=1,
    head_dim=128,
    d_ff=8192,
    vocab_size=65536,
    act="swiglu",
    norm="rmsnorm",
    pos_kind="none",
    tie_embeddings=True,
    n_experts=0,
    ssm_state=16,
    d_inner=5120,  # expand=2
    dt_rank=160,
    conv_width=4,
    attn_every=14,
    attn_offset=7,
    ssm_input_norms=True,
    source="hf:ai21labs/AI21-Jamba2-3B",
)
