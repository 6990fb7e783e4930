"""Mamba-1 selective-scan Pallas-TPU kernel (chunked along time).

Tiling: grid = (batch, d_inner blocks, time chunks); time chunks are the
innermost (sequential) grid axis so the SSM state lives in VMEM scratch and
is carried across chunks.

Layout: the state is held as (N, d_block) — the 16 state channels on
sublanes, d_inner on lanes — so every per-step operand is a broadcast of a
(1, d_block) row or an (N, 1) column and no step needs a transpose.  Within
a chunk the recurrence walks ``ROWS``-step row groups: each group's u/dt/B/C
rows are loaded at an 8-aligned dynamic sublane offset, the ``ROWS`` steps
are unrolled with static slices, and the group's outputs are written back
as one aligned (ROWS, d_block) block.  Mosaic rejects per-step reads at an
unaligned dynamic sublane offset, which is what the row groups avoid.

``chunk`` is a schedule-space knob: larger chunks amortize grid overhead and
HBM→VMEM block transfers; smaller chunks shrink the VMEM working set
(u/dt/y blocks are (chunk × d_block)).  ``chunk`` must be a multiple of
``ROWS``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8  # time steps per aligned row group (one f32 sublane tile)


def _scan_kernel(
    u_ref,  # (1, chunk, d_block)
    dt_ref,  # (1, chunk, d_block)
    a_ref,  # (N, d_block)   A transposed
    b_ref,  # (1, chunk, N)
    c_ref,  # (1, chunk, N)
    d_ref,  # (1, d_block)
    y_ref,  # (1, chunk, d_block)
    x_ref,  # scratch (N, d_block) f32
    *,
    chunk: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        x_ref[...] = jnp.zeros_like(x_ref)

    a = a_ref[...].astype(jnp.float32)  # (N, d_block)
    dvec = d_ref[...].astype(jnp.float32)  # (1, d_block)
    n = a.shape[0]
    # identity mask: turns a (1, N) row into an (N, 1) column with a lane
    # reduction instead of a transpose
    eye = (
        jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    )
    row_id = jax.lax.broadcasted_iota(jnp.int32, (ROWS, 1), 0)

    def column(row):  # (1, N) -> (N, 1)
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    def group(g, x):
        rows = pl.ds(pl.multiple_of(g * ROWS, ROWS), ROWS)
        u = u_ref[0, rows, :].astype(jnp.float32)  # (ROWS, d_block)
        dt = dt_ref[0, rows, :].astype(jnp.float32)
        bm = b_ref[0, rows, :].astype(jnp.float32)  # (ROWS, N)
        cm = c_ref[0, rows, :].astype(jnp.float32)
        y = jnp.zeros(u.shape, jnp.float32)
        for i in range(ROWS):
            u_i, dt_i = u[i : i + 1], dt[i : i + 1]  # (1, d_block)
            b_i = column(bm[i : i + 1])  # (N, 1)
            c_i = column(cm[i : i + 1])
            x = jnp.exp(dt_i * a) * x + b_i * (dt_i * u_i)  # (N, d_block)
            y_i = jnp.sum(x * c_i, axis=0, keepdims=True) + dvec * u_i
            y = jnp.where(row_id == i, y_i, y)
        y_ref[0, rows, :] = y.astype(y_ref.dtype)
        return x

    x_ref[...] = jax.lax.fori_loop(0, chunk // ROWS, group, x_ref[...])


@functools.partial(jax.jit, static_argnames=("chunk", "d_block", "interpret"))
def selective_scan(
    u: jax.Array,  # (B, L, Di)
    dt: jax.Array,  # (B, L, Di)
    A: jax.Array,  # (Di, N)
    Bm: jax.Array,  # (B, L, N)
    Cm: jax.Array,  # (B, L, N)
    D: jax.Array,  # (Di,)
    *,
    chunk: int = 128,
    d_block: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, L, Di = u.shape
    N = A.shape[1]
    chunk = min(chunk, L)
    d_block = min(d_block, Di)
    assert L % chunk == 0 and Di % d_block == 0, (L, chunk, Di, d_block)
    assert chunk % ROWS == 0, (chunk, ROWS)
    grid = (B, Di // d_block, L // chunk)

    kernel = functools.partial(_scan_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda b, di, c: (b, c, di)),
            pl.BlockSpec((1, chunk, d_block), lambda b, di, c: (b, c, di)),
            pl.BlockSpec((N, d_block), lambda b, di, c: (0, di)),
            pl.BlockSpec((1, chunk, N), lambda b, di, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, di, c: (b, c, 0)),
            pl.BlockSpec((1, d_block), lambda b, di, c: (0, di)),
        ],
        out_specs=pl.BlockSpec((1, chunk, d_block), lambda b, di, c: (b, c, di)),
        out_shape=jax.ShapeDtypeStruct((B, L, Di), u.dtype),
        scratch_shapes=[pltpu.VMEM((N, d_block), jnp.float32)],
        interpret=interpret,
        name="selective_scan",
    )(u, dt, A.T, Bm, Cm, D.reshape(1, Di))


# re-exported from the jax-free geometry module
from repro.kernels.geometry import scan_vmem_bytes as vmem_bytes  # noqa: E402
