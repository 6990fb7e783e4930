"""The program's names on a profiler trace (``repro/runtime/tracing.py``).

Host spans of ``Trainer.run`` and ``ServingEngine`` on a CPU trace, read by
the benchmark's trace reader; the engine's counts and request times; and
layer scopes that change the compiled programs' metadata and nothing else.
"""
import contextlib
import os
import re
import sys
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import scopes  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import InputShape  # noqa: E402
from repro.core.space import SchedulePlan  # noqa: E402
from repro.models import transformer  # noqa: E402
from repro.runtime import tracing  # noqa: E402
from repro.serving.engine import ServingEngine  # noqa: E402
from repro.training import optimizer as optim  # noqa: E402
from repro.training.train_step import make_prefill_step, make_train_step  # noqa: E402

PROMPTS = [np.arange(1, n + 1, dtype=np.int32) for n in (3, 5, 2, 4, 1)]


def _spans(trace_dir) -> list:
    return scopes.load(str(trace_dir))["program_spans"]


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]


def _serve(tmp_path, arch, slots=2):
    cfg = get_config(arch).reduced()
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, batch_slots=slots, max_len=32)
    for p in PROMPTS:
        eng.submit(p, max_new_tokens=3)
    with jax.profiler.trace(str(tmp_path / "serve")):
        done = eng.run()
    return eng, done, _spans(tmp_path / "serve")


def test_trainer_spans_one_sync_per_step(tmp_path):
    from repro.training.trainer import Trainer, TrainerConfig

    cfg = get_config("granite-3-2b").reduced()
    tc = TrainerConfig(total_steps=3, ckpt_every=2, ckpt_dir=str(tmp_path / "ckpt"),
                       log_every=1, ckpt_async=False)
    tr = Trainer(cfg, InputShape("t", 32, 2, "train"),
                 SchedulePlan(microbatches=1, remat="none"), tc)
    with jax.profiler.trace(str(tmp_path / "train")):
        tr.run()
    spans = sorted(_spans(tmp_path / "train"), key=lambda s: s[1])
    n = Counter(s[0] for s in spans)
    assert n == {"repro.train.batch": 3, "repro.train.dispatch": 3, "repro.train.sync": 3,
                 "repro.train.log": 3, "repro.train.ckpt": 1}
    # each step: batch, dispatch, sync, log in that order (the checkpoint
    # after step 2)
    order = [s[0][len("repro.train."):] for s in spans]
    assert order == ["batch", "dispatch", "sync", "log"] * 2 + ["ckpt"] + [
        "batch", "dispatch", "sync", "log"]


# the dense arch puts its prompts into the cache in chunk calls; the Mamba
# arch feeds them one token per decode call
ADMISSION_PATHS = pytest.mark.parametrize("arch,path", [
    ("granite-3-2b", "prefill"), ("falcon-mamba-7b", "feed")], ids=["chunk", "per_token"])


@ADMISSION_PATHS
def test_engine_feed_spans_nest_in_admissions(tmp_path, arch, path):
    eng, done, spans = _serve(tmp_path, arch)
    assert len(done) == len(PROMPTS)
    by = {k: [s for s in spans if s[0] == f"repro.serve.{k}"]
          for k in ("admit", "prefill", "feed", "decode", "sync", "bookkeep")}
    fed = by[path]
    assert len(fed) == (sum(len(p) > 1 for p in PROMPTS) if path == "prefill"
                        else sum(len(p) - 1 for p in PROMPTS))
    assert not by["feed" if path == "prefill" else "prefill"]
    assert len(by["admit"]) == len(PROMPTS)
    assert all(any(_inside(f, a) for a in by["admit"]) for f in fed)
    assert len(by["decode"]) == len(by["sync"]) == len(by["bookkeep"]) == \
        eng.stats()["decode_calls"]
    # no engine step runs inside an admission
    assert not any(_inside(d, a) for d in by["decode"] for a in by["admit"])


@ADMISSION_PATHS
def test_engine_counts_match_the_harness_feed_count(tmp_path, arch, path):
    """The prompt tokens the engine put into caches, by chunk calls
    (``prefill_tokens``) or one-token feeds (``feed_calls``), are the count
    the benchmark derives from the prompt lengths it admitted
    (``feed_steps``: each prompt token but the last)."""
    eng, done, _ = _serve(tmp_path, arch)
    feed_steps = sum(len(q.prompt) - 1 for q in done)
    st = eng.stats()
    if path == "prefill":
        assert st["prefill_tokens"] == feed_steps and st["feed_calls"] == 0
        assert st["prefill_calls"] == sum(len(p) > 1 for p in PROMPTS)
    else:
        assert st["feed_calls"] == feed_steps
        assert st["prefill_calls"] == st["prefill_tokens"] == 0
    assert st["admissions"] == st["slot_resets"] == len(PROMPTS)
    assert st["decode_calls"] >= 3 and st["active"] == st["queued"] == 0
    for q in done:
        assert q.submitted_s <= q.admitted_s <= q.first_token_s


# -- layer scopes --------------------------------------------------------------
def _instructions(text: str) -> list:
    """The compiled module's instructions with their metadata removed."""
    out, table = [], False  # the stack-frame tables are metadata too
    for line in text.splitlines():
        table = line.startswith(("FileNames", "FunctionNames", "FileLocations",
                                 "StackFrames")) or (table and line != "")
        if not table:
            out.append(re.sub(r",? metadata=\{[^}]*\}", "", line))
    return out


def _compiled_programs() -> dict:
    """Train, prefill and decode of a tiny MoE config, and prefill and the
    engine's chunk program of a tiny dense one, compiled for the CPU."""
    moe = get_config("granite-moe-1b-a400m").reduced()
    dense = get_config("granite-3-2b").reduced()
    plan = SchedulePlan(microbatches=1, remat="full")
    oc = optim.OptimizerConfig()
    B, S = 1, 32
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)

    def params(cfg):
        return jax.eval_shape(lambda k: transformer.init_params(cfg, k), jax.random.PRNGKey(0))

    p = params(moe)
    o = jax.eval_shape(lambda q: optim.init_opt_state(q, oc), p)
    out = {
        "train": jax.jit(make_train_step(moe, InputShape("t", S, B, "train"), plan, oc)).lower(
            p, o, {"inputs": tok, "labels": tok, "positions": tok}),
        "prefill": jax.jit(make_prefill_step(moe, InputShape("p", S, B, "prefill"), plan)).lower(
            p, {"inputs": tok, "positions": tok}),
        "prefill_dense": jax.jit(make_prefill_step(
            dense, InputShape("p", S, B, "prefill"), plan)).lower(
            params(dense), {"inputs": tok, "positions": tok}),
    }
    eng = ServingEngine(moe, transformer.init_params(moe, jax.random.PRNGKey(0)),
                        batch_slots=2, max_len=S)
    out["decode"] = eng._decode.lower(eng.params, eng.cache, jnp.zeros(2, jnp.int32),
                                      jnp.zeros(2, jnp.int32), jnp.ones(2, bool))
    eng = ServingEngine(dense, transformer.init_params(dense, jax.random.PRNGKey(0)),
                        batch_slots=2, max_len=S)
    out["prefill_chunk"] = eng._prefill.lower(eng.params, eng.cache,
                                              jnp.zeros((1, eng.chunk), jnp.int32),
                                              jnp.int32(0), jnp.int32(1))
    return {k: v.compile().as_text() for k, v in out.items()}


def test_scopes_change_metadata_only(monkeypatch):
    scoped = _compiled_programs()
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = _compiled_programs()
    for k in scoped:
        assert _instructions(scoped[k]) == _instructions(plain[k]), k
    names = set()
    for text in scoped.values():
        for path in re.findall(r'op_name="([^"]*)"', text):
            names.update(n for c in path.split("/") for n in scopes._names(c))
    assert set(tracing.LAYER_SCOPES) <= names
    assert not set(tracing.LAYER_SCOPES) & {
        n for text in plain.values() for path in re.findall(r'op_name="([^"]*)"', text)
        for c in path.split("/") for n in scopes._names(c)}


def test_benchmark_reads_every_layer_scope():
    assert scopes.LAYER_KINDS == tracing.LAYER_SCOPES
    assert scopes.PROGRAM_PREFIX == tracing.SPAN_PREFIX


def test_scope_names_are_a_closed_set():
    with pytest.raises(ValueError):
        with tracing.scope("attn"):
            pass
