"""The names the program gives its work on a profiler trace.

Two kinds, both on the profiler's own clock:

* **Host spans** (``span``): ``jax.profiler.TraceAnnotation("repro.<name>")``
  around host work in ``Trainer.run`` and ``ServingEngine`` (``train.batch``,
  ``train.dispatch``, ``train.sync``, ``train.log``, ``train.ckpt``;
  ``serve.admit``, ``serve.prefill``, ``serve.feed``, ``serve.decode``,
  ``serve.sync``, ``serve.bookkeep``).  With no profiler session running a
  span records nothing.
* **Layer scopes** (``scope``): ``jax.named_scope`` over the device work of
  one layer kind.  A scope lives only in the compiled program's metadata
  (each instruction's ``op_name`` path), so it changes no instruction; the
  trace carries the path beside each device op.  The backward of an op
  carries the same scope under ``transpose(...)``; the oracle-VJP backwards
  of the Pallas kernels carry ``kernel_bwd_<kernel>`` (``kernels/ops.py``),
  and each kernel's instruction is named after the kernel
  (``pallas_call(name=...)``).

There is no switch: a trace is taken with ``jax.profiler.trace(dir)``.
"""
from __future__ import annotations

import contextlib

import jax

SPAN_PREFIX = "repro."

# layer kinds: the closed set of scope names the model step uses
EMBED = "embed"
NORM = "norm"
ATTENTION = "attention"  # projections, the attention kernel, output projection
KV_WRITE = "kv_write"  # decode: the new token's K/V rows written in place
MLP = "mlp"
MOE_ROUTE = "moe_route"  # router logits, softmax, top-k
MOE_DISPATCH = "moe_dispatch"  # sort by expert through the grouped scatter-add
MOE_EXPERTS = "moe_experts"  # the expert GEMMs and their activation
MOE_COMBINE = "moe_combine"  # weighted gather back to token order
LOGITS = "logits"
LOSS = "loss"
OPTIMIZER = "optimizer"  # gradient clip and AdamW
CACHE_COMMIT = "cache_commit"  # decode: a slot's new rows or state kept or dropped
SAMPLE = "sample"

LAYER_SCOPES = (EMBED, NORM, ATTENTION, KV_WRITE, MLP, MOE_ROUTE, MOE_DISPATCH,
                MOE_EXPERTS, MOE_COMBINE, LOGITS, LOSS, OPTIMIZER, CACHE_COMMIT, SAMPLE)


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span ``repro.<name>`` on the profiler's trace."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


@contextlib.contextmanager
def scope(name: str):
    """The device scope of one layer kind (``LAYER_SCOPES``); also a
    function decorator."""
    if name not in LAYER_SCOPES:
        raise ValueError(f"{name!r} is not a layer scope")
    with jax.named_scope(name):
        yield
