"""Fused RMSNorm Pallas-TPU kernel.

Row-tiled: grid over blocks of rows; each block loads (block_rows × d) into
VMEM once, reduces in f32 on the VPU, scales, and writes back — one HBM
round-trip instead of the three (square, mean, scale) an unfused lowering
would do for large d.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # (block_rows, d)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * w_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "eps", "interpret"))
def rmsnorm(
    x: jax.Array,  # (..., d)
    w: jax.Array,  # (d,)
    *,
    block_rows: int = 256,
    eps: float = 1e-6,
    interpret: bool = False,
) -> jax.Array:
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    block_rows = min(block_rows, rows)
    # pad rows to a multiple of block_rows
    pad = (-rows) % block_rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    grid = (x2.shape[0] // block_rows,)
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda r: (r, 0)),
            pl.BlockSpec((1, d), lambda r: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
        name="rmsnorm",
    )(x2, w.reshape(1, d))
    if pad:
        out = out[:rows]
    return out.reshape(orig_shape)
