"""Main-path Pallas kernels compiled for a described TPU v5e chip.

Nothing runs: each test lowers a kernel at the widths of a config the repo
serves and compiles it with the TPU compiler for one chip of a ``v5e:2x2``
topology that is described, not attached.  This catches what interpret mode
cannot — unaligned slices, VMEM overuse, lowering failures — at no chip
time.  The topology is described inside a module fixture, never at import,
because only one process may load the TPU library at a time.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gemm import moe_gemm
from repro.kernels.quantize import dequantize_int8, quantize_int8
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.selective_scan import selective_scan

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *specs):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# granite-3-2b: 32 query / 8 kv heads, head_dim 64, 4096 tokens
ATTN = [((1, 32, 4096, 64), BF16), ((1, 8, 4096, 64), BF16), ((1, 8, 4096, 64), BF16)]


@pytest.mark.parametrize("block", [256, 512])
def test_flash_attention_compiles(one_chip, block):
    fn = functools.partial(flash_attention, block_q=block, block_kv=block)
    _compile(fn, one_chip, *ATTN)


def test_selective_scan_compiles_falcon_mamba(one_chip):
    # falcon-mamba-7b: d_inner 8192, N 16, 4096 tokens, chunk 128, d_block 256
    L, Di, N = 4096, 8192, 16
    fn = functools.partial(selective_scan, chunk=128, d_block=256)
    _compile(fn, one_chip, ((1, L, Di), BF16), ((1, L, Di), BF16),
             ((Di, N), jnp.float32), ((1, L, N), BF16), ((1, L, N), BF16),
             ((Di,), jnp.float32))


@pytest.mark.parametrize("chunk", [128, 256])
def test_selective_scan_compiles_jamba2(one_chip, chunk):
    # jamba2-3b's prefill cell: d_inner 5120, N 16, 8192 tokens, d_block 256
    L, Di, N = 8192, 5120, 16
    fn = functools.partial(selective_scan, chunk=chunk, d_block=256)
    _compile(fn, one_chip, ((1, L, Di), BF16), ((1, L, Di), BF16),
             ((Di, N), jnp.float32), ((1, L, N), BF16), ((1, L, N), BF16),
             ((Di,), jnp.float32))


@pytest.mark.parametrize("d,f", [(1024, 512), (512, 1024)])
def test_moe_gemm_compiles_granite_moe(one_chip, d, f):
    # granite-moe-1b-a400m: 32 experts, capacity 1280 for 4096 tokens top-8
    fn = functools.partial(moe_gemm, block_c=128, block_f=256, block_d=256)
    _compile(fn, one_chip, ((32, 1280, d), BF16), ((32, d, f), BF16))


def test_rmsnorm_compiles(one_chip):
    _compile(rmsnorm, one_chip, ((1, 4096, 2048), BF16), ((2048,), BF16))


def test_quantize_compiles(one_chip):
    # a 2048 x 8192 gradient in the compressed all-reduce's (rows, 128) layout
    rows = 2048 * 8192 // 128
    _compile(quantize_int8, one_chip, ((rows, 128), jnp.float32))
    _compile(dequantize_int8, one_chip, ((rows, 128), jnp.int8),
             ((rows, 1), jnp.float32))


def _vjp_of(kernel, oracle, name):
    def f(*args):
        out, vjp = jax.vjp(
            lambda *a: ops._pallas_with_ref_vjp(name, kernel, oracle, *a), *args
        )
        return out, vjp(jnp.ones_like(out))

    return f


def test_rmsnorm_custom_vjp_backward_compiles(one_chip):
    f = _vjp_of(rmsnorm, ref.rmsnorm, "rmsnorm")
    c = _compile(f, one_chip, ((1, 4096, 2048), BF16), ((2048,), BF16))
    assert "kernel_bwd_rmsnorm" in c.as_text()


def test_flash_attention_custom_vjp_backward_compiles(one_chip):
    kernel = functools.partial(flash_attention, block_q=256, block_kv=256)
    f = _vjp_of(kernel, ref.attention, "attention")
    c = _compile(f, one_chip, *ATTN)
    assert "kernel_bwd_attention" in c.as_text()


# each kernel's instruction is named by its ``pallas_call(name=...)``: the
# device trace and the roofline metrics find it by that name, whatever the
# Python function or the scope around it is called
@pytest.mark.parametrize("kernel,shapes", [
    (functools.partial(flash_attention.__wrapped__, block_q=128, block_kv=128),
     [((1, 4, 256, 64), BF16), ((1, 2, 256, 64), BF16), ((1, 2, 256, 64), BF16)]),
    (functools.partial(moe_gemm.__wrapped__, block_c=128, block_f=256, block_d=256),
     [((4, 128, 256), BF16), ((4, 256, 256), BF16)]),
    (rmsnorm.__wrapped__, [((1, 256, 256), BF16), ((256,), BF16)]),
    (functools.partial(selective_scan.__wrapped__, chunk=128, d_block=256),
     [((1, 256, 256), BF16), ((1, 256, 256), BF16), ((256, 16), jnp.float32),
      ((1, 256, 16), BF16), ((1, 256, 16), BF16), ((256,), jnp.float32)]),
    (quantize_int8.__wrapped__, [((256, 128), jnp.float32)]),
    (dequantize_int8.__wrapped__, [((256, 128), jnp.int8), ((256, 1), jnp.float32)]),
], ids=["flash_attention", "moe_gemm", "rmsnorm", "selective_scan", "quantize_int8",
        "dequantize_int8"])
def test_kernel_instruction_carries_its_name(one_chip, request, kernel, shapes):
    name = request.node.callspec.id

    def elsewhere(*args):
        with jax.named_scope("elsewhere"):
            return kernel(*args)

    text = _compile(elsewhere, one_chip, *shapes).as_text()
    calls = [l for l in text.splitlines() if "custom_call_target=\"tpu_custom_call\"" in l]
    assert calls and all(re.search(rf"%{name}(\.\d+)? = ", l) for l in calls), calls


def _unfused_instructions(hlo: str) -> list:
    """The instructions of a compiled module that run as ops of their own:
    those of every computation but the bodies of fusions."""
    comps = re.split(r"\n(?=%[\w.\-]+ \(|ENTRY )", hlo)
    fused = set(re.findall(r" fusion\(.*?calls=%([\w.\-]+)", hlo))
    return [line for comp in comps
            if not (m := re.match(r"%([\w.\-]+) ", comp)) or m.group(1) not in fused
            for line in comp.splitlines()]


def _serving_engine(one_chip, monkeypatch):
    """The engine at the serving cell's size (granite-3-2b, 16 slots x
    2048), its params and cache as shapes on the described chip."""
    from repro.configs import get_config
    from repro.models import transformer
    from repro.serving.engine import ServingEngine

    cfg, B, L = get_config("granite-3-2b"), 16, 2048
    make = transformer.init_cache
    monkeypatch.setattr(transformer, "init_cache",
                        lambda *a, **k: jax.eval_shape(lambda: make(*a, **k)))
    params = jax.eval_shape(lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    eng = ServingEngine(cfg, params, batch_slots=B, max_len=L)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    cache_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(eng.cache))
    assert cache_bytes == 2_684_354_560
    return eng, on_chip(params), on_chip(eng.cache), cache_bytes


def _cache_copies(hlo: str, B: int = 16, L: int = 2048) -> list:
    """Ops of their own that copy, transpose or select a cache, a layer's
    slab or one slot's slab."""
    shaped = rf"= bf16\[(40,)?(1,)?({B}|1),8,{L},64\]\S* (copy|copy-start|transpose|select)\("
    return [l for l in _unfused_instructions(hlo) if re.search(shaped, l)]


def test_serve_decode_updates_the_cache_in_place(one_chip, monkeypatch):
    """The engine's decode program at the serving cell's size (granite-3-2b,
    16 slots x 2048) writes the 2,684,354,560 B cache in the buffer it was
    given, and holds no second copy of it or of a layer's 67 MB K/V slab.

    Readings of the compiled program: the engine that committed through a
    whole-cache ``where`` into a fresh cache read ``alias_size_in_bytes`` 0
    and ``temp_size_in_bytes`` 135,251,456 (each layer's slab copied
    between layouts); the in-place one reads the cache's size and
    1,613,824."""
    eng, params, cache, cache_bytes = _serving_engine(one_chip, monkeypatch)
    vec = lambda dt: jax.ShapeDtypeStruct((16,), dt, sharding=one_chip)
    compiled = eng._decode.lower(params, cache, vec(jnp.int32), vec(jnp.int32),
                                 vec(jnp.bool_)).compile()
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes == cache_bytes
    assert ma.temp_size_in_bytes < 16 * 2**20
    whole = _cache_copies(compiled.as_text())
    assert not whole, whole[:3]


def test_serve_prefill_updates_the_cache_in_place(one_chip, monkeypatch):
    """The engine's chunk program at the same size writes its 128 rows of
    every layer into the cache it was given, with no copy of the cache or
    of a slab.  Reading: alias the cache's size, temp 355,328 B."""
    eng, params, cache, cache_bytes = _serving_engine(one_chip, monkeypatch)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    chunk = jax.ShapeDtypeStruct((1, eng.chunk), jnp.int32, sharding=one_chip)
    compiled = eng._prefill.lower(params, cache, chunk, scalar, scalar).compile()
    ma = compiled.memory_analysis()
    assert eng.chunk == 128
    assert ma.alias_size_in_bytes == cache_bytes
    assert ma.temp_size_in_bytes < 16 * 2**20
    whole = _cache_copies(compiled.as_text())
    assert not whole, whole[:3]
