"""Seeded model weights in the program's parameter layout, made on the device.

The benchmark makes the weights itself, so that the reference can make the
same ones without taking anything from the program: one jitted call from
the seed, in the dtype the configuration states.  The layout (a stack of
per-layer blocks under ``blocks/b0``, a tied embedding) is the program's
parameter format, as a checkpoint format would be.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: int) -> jax.Array:
    """A PRNG key for one of the run's streams, from a seed of any size."""
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def shapes(m: dict) -> dict:
    """{path: (shape, dtype)} of every parameter of the model in ``m``."""
    d, L, V = m["hidden_size"], m["num_hidden_layers"], m["vocab_size"]
    H, Hkv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    f, dt = m["intermediate_size"], m["dtype"]
    out = {
        "embed": ((V, d), dt),
        "final_norm": ((d,), dt),
        "blocks/b0/norm1": ((L, d), dt),
        "blocks/b0/norm2": ((L, d), dt),
        "blocks/b0/attn/wq": ((L, d, H * hd), dt),
        "blocks/b0/attn/wk": ((L, d, Hkv * hd), dt),
        "blocks/b0/attn/wv": ((L, d, Hkv * hd), dt),
        "blocks/b0/attn/wo": ((L, H * hd, d), dt),
    }
    E = m.get("num_local_experts", 0)
    if E:
        out.update({
            "blocks/b0/mlp/router": ((L, d, E), "float32"),
            "blocks/b0/mlp/w_up": ((L, E, d, f), dt),
            "blocks/b0/mlp/w_gate": ((L, E, d, f), dt),
            "blocks/b0/mlp/w_down": ((L, E, f, d), dt),
        })
    else:
        out.update({
            "blocks/b0/mlp/w_up": ((L, d, f), dt),
            "blocks/b0/mlp/w_gate": ((L, d, f), dt),
            "blocks/b0/mlp/w_down": ((L, f, d), dt),
        })
    return out


def _scale(path: str, m: dict) -> float:
    if path.endswith(("wo", "w_down")):
        return 0.02 / (2 * m["num_hidden_layers"]) ** 0.5
    return 0.02


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        *parents, name = path.split("/")
        node = tree
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = leaf
    return tree


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, path + "/"))
        else:
            out[path] = v
    return out


def make(m: dict, key: jax.Array, sharding=None) -> dict:
    """All parameters from ``key``: normal(0, 0.02) matrices (output
    projections scaled by 1/sqrt(2L)), norm weights near 1."""
    spec = shapes(m)

    def build(key):
        keys = jax.random.split(key, len(spec))
        flat = {}
        for k, (path, (shape, dt)) in zip(keys, sorted(spec.items())):
            x = jax.random.normal(k, shape, jnp.float32)
            if "norm" in path:
                x = 1.0 + 0.1 * x
            else:
                x = x * _scale(path, m)
            flat[path] = x.astype(dt)
        return _nest(flat)

    return jax.jit(build, out_shardings=sharding)(key)
