"""Operations and bytes of a Jamba hybrid's prefill and of its selective
scan, from shapes, counted as ``flops.py`` counts them: a multiply-add is
two operations, bytes are each array read or written once.

Model FLOPs are the matrix products of the forward pass: the Mamba mixers'
in, x, dt and out projections, the attention layers' projections and their
attention over the causal context, every layer's SwiGLU MLP and the tied
output projection.  The Mamba mixers' elementwise work (the depthwise conv,
the dt/B/C norms and the scan's recurrence) is left out of model FLOPs, as
norms and activations are left out of a transformer's; the scan's own work
is counted by ``selective_scan`` for its roofline share.
"""
from __future__ import annotations


def n_attention_layers(m: dict) -> int:
    return m["num_hidden_layers"] // m["attn_layer_period"]


def matmul_params(m: dict) -> int:
    """Matrix parameters one token passes through, forward (the tied output
    projection included)."""
    d, L = m["hidden_size"], m["num_hidden_layers"]
    H, Hkv, D = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    Di, N, R = m["mamba_expand"] * d, m["mamba_d_state"], m["mamba_dt_rank"]
    La = n_attention_layers(m)
    attn = d * H * D * 2 + d * Hkv * D * 2
    mamba = d * 2 * Di + Di * (R + 2 * N) + R * Di + Di * d
    mlp = 3 * d * m["intermediate_size"]
    return La * attn + (L - La) * mamba + L * mlp + d * m["vocab_size"]


def forward_flops_per_seq(m: dict, S: int) -> int:
    """Forward operations of one causal sequence of S tokens: the matrix
    products, and QK^T and PV of the attention layers over the S(S+1)/2
    causal pairs."""
    pairs = S * (S + 1) // 2
    return (2 * matmul_params(m) * S
            + 4 * n_attention_layers(m) * m["num_attention_heads"] * m["head_dim"] * pairs)


def selective_scan(B: int, L: int, Di: int, N: int, itemsize: int = 2):
    """One selective-scan call over (B, L, Di) inputs with N state channels.

    Operations: for each (b, t, d, n), exp(dt*A) (the product and the
    exponential), its product with the state, dt*u*B added into it, and
    C times the state summed into y: 6.  Bytes: u, dt and y of ``itemsize``
    each and B and C of ``itemsize`` read or written once, A (d_inner x N)
    and D (d_inner) in float32."""
    ops = 6 * B * L * Di * N
    nbytes = itemsize * (3 * B * L * Di + 2 * B * L * N) + 4 * (Di * N + Di)
    return ops, nbytes
