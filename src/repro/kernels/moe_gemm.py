"""Grouped (per-expert) matmul Pallas-TPU kernel for capacity-batched MoE.

x: (E, C, d) tokens grouped per expert (padded to capacity C),
w: (E, d, f) expert weights  ->  (E, C, f).

Tiling: grid = (E, C/block_c, f/block_f, d/block_d) with the contraction
axis innermost so a (block_c × block_f) f32 accumulator persists in VMEM
scratch across d-steps.  Every matmul tile is MXU-shaped; block sizes are
schedule knobs (multiples of 128).  Expert-parallel execution shards the E
axis, so the kernel never sees more than E/ep experts per device.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, o_ref, acc_ref):
    di = pl.program_id(3)
    nd = pl.num_programs(3)

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[0].astype(jnp.float32),
        w_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(di == nd - 1)
    def _fin():
        o_ref[0, :, :] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_c", "block_f", "block_d", "interpret")
)
def moe_gemm(
    x: jax.Array,  # (E, C, d)
    w: jax.Array,  # (E, d, f)
    *,
    block_c: int = 128,
    block_f: int = 128,
    block_d: int = 256,
    interpret: bool = False,
) -> jax.Array:
    E, C, d = x.shape
    _, _, f = w.shape
    block_c = min(block_c, C)
    block_f = min(block_f, f)
    block_d = min(block_d, d)
    assert C % block_c == 0 and f % block_f == 0 and d % block_d == 0
    grid = (E, C // block_c, f // block_f, d // block_d)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, block_d), lambda e, c, fo, di: (e, c, di)),
            pl.BlockSpec((1, block_d, block_f), lambda e, c, fo, di: (e, di, fo)),
        ],
        out_specs=pl.BlockSpec(
            (1, block_c, block_f), lambda e, c, fo, di: (e, c, fo)
        ),
        out_shape=jax.ShapeDtypeStruct((E, C, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        interpret=interpret,
        name="moe_gemm",
    )(x, w)
