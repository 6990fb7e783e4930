"""Seeded weights of a Jamba hybrid (Mamba-1 + attention) in the program's
parameter layout, made on the device.

As ``weights.py`` does for the Granite blocks, the benchmark makes the
weights itself, so that the reference makes the same ones without taking
anything from the program.  The layout is the program's parameter format:
each of the period's ``attn_layer_period`` slots is a block ``blocks/b<i>``
stacked over the periods, attention at slot ``attn_layer_offset``, Mamba
mixers at the others, and a dense SwiGLU MLP in every slot.  The published
checkpoint's names map onto it as

    in_proj -> mamba/in_proj (d, 2*d_inner), x then gate
    conv1d.weight (d_inner, 1, K), conv1d.bias -> mamba/conv_w (K, d_inner), conv_b
    x_proj -> mamba/x_proj (d_inner, dt_rank + 2*d_state), dt then B then C
    dt_proj.weight, dt_proj.bias -> mamba/dt_w (dt_rank, d_inner), dt_b
    dt_layernorm, b_layernorm, c_layernorm -> mamba/dt_norm, b_norm, c_norm
    A_log, D, out_proj -> mamba/A_log, Dp, out_proj (d_inner, d)
    q/k/v/o_proj -> attn/wq, wk, wv, wo;  gate/up/down_proj -> mlp/w_gate, w_up, w_down
    input_layernorm, pre_ff_layernorm -> norm1, norm2

with every matrix stored input-major.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.weights import _nest


def kinds(m: dict, attn_first: bool = False) -> list:
    """The mixer of each slot of the period, in the order the layers run:
    attention at ``attn_layer_offset``, or first with ``attn_first`` (a fault
    the benchmark's comparison must catch)."""
    n, off = m["attn_layer_period"], m["attn_layer_offset"]
    order = [off] + [i for i in range(n) if i != off] if attn_first else list(range(n))
    return [(i, "attn" if i == off else "mamba") for i in order]


def shapes(m: dict) -> dict:
    """{path: (shape, dtype)} of every parameter of the model in ``m``."""
    d, V, dt = m["hidden_size"], m["vocab_size"], m["dtype"]
    P = m["num_hidden_layers"] // m["attn_layer_period"]
    H, Hkv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    f, K = m["intermediate_size"], m["mamba_d_conv"]
    Di, N, R = m["mamba_expand"] * d, m["mamba_d_state"], m["mamba_dt_rank"]
    out = {"embed": ((V, d), dt), "final_norm": ((d,), dt)}
    for i, kind in kinds(m):
        b = f"blocks/b{i}/"
        out.update({
            b + "norm1": ((P, d), dt),
            b + "norm2": ((P, d), dt),
            b + "mlp/w_up": ((P, d, f), dt),
            b + "mlp/w_gate": ((P, d, f), dt),
            b + "mlp/w_down": ((P, f, d), dt),
        })
        if kind == "attn":
            out.update({
                b + "attn/wq": ((P, d, H * hd), dt),
                b + "attn/wk": ((P, d, Hkv * hd), dt),
                b + "attn/wv": ((P, d, Hkv * hd), dt),
                b + "attn/wo": ((P, H * hd, d), dt),
            })
        else:
            out.update({
                b + "mamba/in_proj": ((P, d, 2 * Di), dt),
                b + "mamba/conv_w": ((P, K, Di), dt),
                b + "mamba/conv_b": ((P, Di), dt),
                b + "mamba/x_proj": ((P, Di, R + 2 * N), dt),
                b + "mamba/dt_w": ((P, R, Di), dt),
                b + "mamba/dt_b": ((P, Di), dt),
                b + "mamba/A_log": ((P, Di, N), "float32"),
                b + "mamba/Dp": ((P, Di), "float32"),
                b + "mamba/out_proj": ((P, Di, d), dt),
                b + "mamba/dt_norm": ((P, R), dt),
                b + "mamba/b_norm": ((P, N), dt),
                b + "mamba/c_norm": ((P, N), dt),
            })
    return out


def _leaf(key, path: str, shape, m: dict) -> jax.Array:
    """One leaf in float32: normal(0, 0.02) matrices, output projections
    scaled by 1/sqrt(2L), norm weights near 1; the Mamba leaves as the
    published initialisation draws them (A = 1..d_state, D = 1, dt between
    1e-3 and 1e-1 through its bias, dt_proj at dt_rank**-0.5, the depthwise
    conv at its fan-in K**-0.5)."""
    name = path.rsplit("/", 1)[-1]
    x = jax.random.normal(key, shape, jnp.float32)
    if "norm" in name:
        return 1.0 + 0.1 * x
    if name == "A_log":
        return jnp.log(jnp.broadcast_to(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32), shape))
    if name == "Dp":
        return jnp.ones(shape, jnp.float32)
    if name == "dt_b":
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus(dt_b) = dt
    if name in ("wo", "w_down", "out_proj"):
        return x * 0.02 / (2 * m["num_hidden_layers"]) ** 0.5
    if name == "dt_w":
        return x * m["mamba_dt_rank"] ** -0.5
    if name == "conv_w":
        return x * m["mamba_d_conv"] ** -0.5
    return x * 0.02


def make(m: dict, key: jax.Array, sharding=None) -> dict:
    """All parameters from ``key``, each leaf from its own split of it, in
    the dtype ``shapes`` gives."""
    spec = shapes(m)

    def build(key):
        keys = jax.random.split(key, len(spec))
        return _nest({path: _leaf(k, path, shape, m).astype(dt)
                      for k, (path, (shape, dt)) in zip(keys, sorted(spec.items()))})

    return jax.jit(build, out_shardings=sharding)(key)
