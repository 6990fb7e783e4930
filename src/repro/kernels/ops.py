"""Public jit'd kernel entry points with backend dispatch.

Three modes (``set_kernel_mode`` / ``kernel_mode`` context manager):

* ``auto``      — Pallas kernels on TPU, jnp oracles elsewhere (default).
                  This is what the models call: on the CPU-only container the
                  oracle path lowers to the same dot-products so dry-run
                  ``cost_analysis`` FLOPs/bytes are representative, while on a
                  real TPU pod the Pallas kernels run.
* ``interpret`` — Pallas kernels in interpret mode (CPU correctness tests).
* ``ref``       — force the jnp oracles.

Kernel block shapes are threaded from the schedule plan (``KernelTiles``).

The Pallas forwards of ``attention``, ``selective_scan``, ``rmsnorm`` and
``moe_gemm`` are differentiable: each is a ``jax.custom_vjp`` whose backward
pass is the VJP of the matching ``ref.py`` oracle, traced under the named
scope ``kernel_bwd_<name>`` so a profile shows it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import moe_gemm as _mg
from repro.kernels import quantize as _qt
from repro.kernels import ref as _ref
from repro.kernels import rmsnorm as _rn
from repro.kernels import selective_scan as _ss

_state = threading.local()


@dataclasses.dataclass(frozen=True)
class KernelTiles:
    """Schedule-tunable kernel block shapes."""

    attn_block_q: int = 256
    attn_block_kv: int = 256
    scan_chunk: int = 128
    scan_d_block: int = 256
    moe_block_c: int = 128
    moe_block_f: int = 256
    moe_block_d: int = 256


DEFAULT_TILES = KernelTiles()


def set_kernel_mode(mode: str) -> None:
    assert mode in ("auto", "interpret", "ref"), mode
    _state.mode = mode


def get_kernel_mode() -> str:
    return getattr(_state, "mode", "auto")


@contextlib.contextmanager
def kernel_mode(mode: str):
    prev = get_kernel_mode()
    set_kernel_mode(mode)
    try:
        yield
    finally:
        set_kernel_mode(prev)


def _use_pallas() -> bool:
    mode = get_kernel_mode()
    if mode == "ref":
        return False
    if mode == "interpret":
        return True
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return get_kernel_mode() == "interpret"


def _per_device(name: str, kernel, shard, args):
    """``kernel`` as one call per device when ``shard`` carries a mesh
    (``sharding.rules.ShardFn``): XLA cannot partition a Mosaic kernel, so
    it runs under ``shard_map`` on the splits ``ShardingRules.kernel_specs``
    names."""
    rules = getattr(shard, "rules", None)
    if rules is None:
        return kernel
    in_specs, out_specs = rules.kernel_specs(name, *(a.shape for a in args))
    return jax.shard_map(kernel, mesh=shard.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _pallas_with_ref_vjp(name: str, kernel, oracle, *args, shard=None):
    """``kernel(*args)`` forward; backward is ``jax.vjp(oracle, *args)``.

    The residuals are the inputs: the backward recomputes the oracle's
    forward and differentiates it, partitioned by XLA like any jnp code."""

    kernel = _per_device(name, kernel, shard, args)

    @jax.custom_vjp
    def f(*a):
        return kernel(*a)

    def f_fwd(*a):
        return kernel(*a), a

    def f_bwd(a, g):
        with jax.named_scope(f"kernel_bwd_{name}"):
            return jax.vjp(oracle, *a)[1](g)

    f.defvjp(f_fwd, f_bwd)
    return f(*args)


# -- attention ---------------------------------------------------------------
def attention(q, k, v, *, causal=True, tiles: KernelTiles = DEFAULT_TILES,
              shard=None):
    if _use_pallas():
        kernel = functools.partial(
            _fa.flash_attention,
            causal=causal,
            block_q=tiles.attn_block_q,
            block_kv=tiles.attn_block_kv,
            interpret=_interpret(),
        )
        oracle = functools.partial(_ref.attention, causal=causal)
        return _pallas_with_ref_vjp("attention", kernel, oracle, q, k, v,
                                    shard=shard)
    # kernel_streamed: on the TPU target this region is the flash-attention
    # Pallas kernel — its interior (S² scores chain) never touches HBM, so
    # the HLO byte analysis (core/hlo_analysis.py) excludes ops under this
    # scope from the memory-roofline term.
    with jax.named_scope("kernel_streamed_attention"):
        return _ref.attention(q, k, v, causal=causal)


# -- mamba scan ----------------------------------------------------------------
def selective_scan(u, dt, A, Bm, Cm, D, *, tiles: KernelTiles = DEFAULT_TILES,
                   shard=None):
    if _use_pallas():
        kernel = functools.partial(
            _ss.selective_scan,
            chunk=tiles.scan_chunk,
            d_block=tiles.scan_d_block,
            interpret=_interpret(),
        )
        return _pallas_with_ref_vjp(
            "selective_scan", kernel, _ref.selective_scan, u, dt, A, Bm, Cm, D,
            shard=shard,
        )
    # kernel_streamed: the Pallas scan kernel carries the SSM state in VMEM
    with jax.named_scope("kernel_streamed_scan"):
        return _ref.selective_scan(u, dt, A, Bm, Cm, D)


selective_scan_step = _ref.selective_scan_step  # decode step: pure jnp


# -- rmsnorm -------------------------------------------------------------------
def rmsnorm(x, w, *, eps: float = 1e-6, shard=None):
    if _use_pallas():
        kernel = functools.partial(_rn.rmsnorm, eps=eps, interpret=_interpret())
        oracle = functools.partial(_ref.rmsnorm, eps=eps)
        return _pallas_with_ref_vjp("rmsnorm", kernel, oracle, x, w, shard=shard)
    return _ref.rmsnorm(x, w, eps=eps)


# -- moe grouped gemm -----------------------------------------------------------
def moe_gemm(x, w, *, tiles: KernelTiles = DEFAULT_TILES, shard=None):
    if _use_pallas():
        kernel = functools.partial(
            _mg.moe_gemm,
            block_c=tiles.moe_block_c,
            block_f=tiles.moe_block_f,
            block_d=tiles.moe_block_d,
            interpret=_interpret(),
        )
        return _pallas_with_ref_vjp("moe_gemm", kernel, _ref.moe_gemm, x, w,
                                    shard=shard)
    return _ref.moe_gemm(x, w)


# -- int8 quant ------------------------------------------------------------------
def quantize_int8(x):
    if _use_pallas():
        return _qt.quantize_int8(x, interpret=_interpret())
    return _ref.quantize_int8(x)


def dequantize_int8(q, scale, dtype=jnp.float32):
    if _use_pallas():
        return _qt.dequantize_int8(q, scale, dtype=dtype, interpret=_interpret())
    return _ref.dequantize_int8(q, scale, dtype=dtype)
