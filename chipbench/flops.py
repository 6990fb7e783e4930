"""Operations and bytes of each kernel call and each model step, from shapes.

These are the work the algorithm needs, counted by hand: a multiply-add is
two operations, bytes are each array read or written once.  Model FLOPs
count the matrix products of the forward pass (x3 for forward and backward
in training), with attention over the causal context and no recomputation.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(chips: int = 1) -> dict:
    """The peak table's entry for the device JAX finds; an unknown kind is an
    error, not a default."""
    import jax

    kind = jax.devices()[0].device_kind
    table = json.loads(PEAKS.read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS.name}")
    return dict(table[kind], kind=kind, chips=chips)


def moe_gemm(E: int, C: int, d: int, f: int, itemsize: int = 2):
    """(E, C, d) @ (E, d, f) -> (E, C, f)."""
    return 2 * E * C * d * f, itemsize * (E * C * d + E * d * f + E * C * f)


def flash_attention(B: int, H: int, Hkv: int, S: int, D: int, itemsize: int = 2):
    """Causal attention of S queries over S keys: QK^T and PV over the
    S(S+1)/2 causal pairs; q, k, v read and o written once."""
    pairs = S * (S + 1) // 2
    return 4 * B * H * D * pairs, itemsize * (2 * B * H * S * D + 2 * B * Hkv * S * D)


def matmul_params(m: dict) -> int:
    """Matrix parameters one token passes through, forward (active experts
    only; the router included; the tied output projection included)."""
    d, L = m["hidden_size"], m["num_hidden_layers"]
    H, Hkv, D = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    attn = d * H * D * 2 + d * Hkv * D * 2
    f = m["intermediate_size"]
    E = m.get("num_local_experts", 0)
    mlp = (d * E + m["num_experts_per_tok"] * 3 * d * f) if E else 3 * d * f
    return L * (attn + mlp) + d * m["vocab_size"]


def attention_flops(m: dict, context: int) -> int:
    """Forward attention operations of one token attending to ``context``
    positions (itself included), over all layers."""
    return 4 * m["num_hidden_layers"] * m["num_attention_heads"] * m["head_dim"] * context


def forward_flops_per_seq(m: dict, S: int) -> int:
    """Forward operations of one causal sequence of S tokens."""
    return 2 * matmul_params(m) * S + attention_flops(m, S * (S + 1) // 2)


def train_flops_per_token(m: dict, S: int) -> float:
    """Forward and backward (3x forward) per token of S-token sequences."""
    return 3 * forward_flops_per_seq(m, S) / S


def roofline_s(flops: float, nbytes: float, pk: dict) -> float:
    """Least time the chip could take for the work: the larger of compute
    and memory time at peak."""
    return max(flops / pk["bf16_flops_s"], nbytes / pk["hbm_bytes_s"])
