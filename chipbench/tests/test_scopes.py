"""The layer-kind reduction (``scopes.py``): on hand-made events, on the two
unscoped chip traces ``trace.py`` is checked on, and on three scoped chip
traces of the program's 2-layer cuts (``record_scoped_trace.py``)."""
import gzip
import json
import math
import pathlib
import re
from types import SimpleNamespace

import pytest

from chipbench import scopes, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"

# trace.reduce's numbers for the two unscoped fixtures, as the benchmark has
# read them since they were recorded
PINNED = {
    "prefill_2l": {"window_s": 0.081890352, "busy_s": 0.049454448000000005,
                   "idle_share": 0.39608944408982383, "collective_exposed_s": 0.0,
                   "idle_by_span": {"batch": 0.027605556999999944,
                                    "prefill_call": 0.002996195999999874,
                                    "outside spans": 0.001834151}},
    "train_2l": {"window_s": 0.11780684000000001, "busy_s": 0.087865858,
                 "idle_share": 0.25415317141177884, "collective_exposed_s": 0.0,
                 "idle_by_span": {"train_chunk": 0.029940981999998846}},
}


def _load(name):
    with gzip.open(DATA / f"{name}.json.gz", "rt") as f:
        return json.load(f)


def _op(name):
    return f"%{name}.1 = f32[8] custom-call()"


@pytest.mark.parametrize("name", ["prefill_2l", "train_2l"])
def test_unscoped_trace_reduces_as_before(name):
    """A program that names nothing reads exactly as ``trace.reduce`` reads
    it: every key, the breakdown, the labels; all of it under ``unscoped``."""
    tr = _load(name)
    old, new = trace.reduce(tr, 1), scopes.reduce(tr, 1)
    for k, v in PINNED[name].items():
        assert old[k] == v and new[k] == v, k
    scoped = new["breakdown"].pop("device_scopes")
    for k in old:
        assert new[k] == old[k], k
    assert set(new) - set(old) == {"device_by_scope"}
    assert scoped == [[scopes.UNSCOPED, new["device_by_scope"][scopes.UNSCOPED]]]
    assert list(new["device_by_scope"]) == [scopes.UNSCOPED]
    assert new["device_by_scope"][scopes.UNSCOPED] == pytest.approx(new["busy_s"], rel=1e-9)


@pytest.mark.parametrize("path,kind", [
    ("jit(train_step)/jvp()/while/body/closed_call/moe_route/top_k", "moe_route"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/moe_dispatch/jit(argsort)/iota", "moe_dispatch.bwd"),
    ("jit(train_step)/transpose(jvp(logits))/norm/mul", "norm.bwd"),
    ("jit(train_step)/jvp(logits)/norm/rsqrt", "norm"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/attention/"
     "kernel_bwd_attention/dot_general", "kernel_bwd_attention"),
    ("jit(train_step)/jvp(loss)/jit(take_along_axis)/select_n", "loss"),
    ("jit(_decode)/cache_commit/jit(_where)/select_n", "cache_commit"),
    ("jit(prefill_step)/while/body/closed_call/attention/jit(flash_attention)/"
     "flash_attention/pallas_call", "attention"),
    ("params['blocks']['b0']['mlp']['w_up']", "unscoped"),
    ("jit(train_step)/rmsnorm/mul", "unscoped"),
    ("", "unscoped"),
])
def test_kind_of_a_path(path, kind):
    assert scopes.kind(path) == kind


def test_reduce_by_hand():
    """One device, a 100 ns window.  Ops: route 0-10, its backward 10-20,
    a kernel's oracle backward 20-30, an XLA copy 30-40 with no scope, the
    optimizer 60-80.  The gap 40-60 falls in the program span
    ``train.sync`` inside the harness's ``train_chunk``; 80-100 in the
    harness span alone."""
    tr = {
        "devices": {"/device:TPU:0": [[_op("fusion"), 0, 10], [_op("fusion"), 10, 10],
                                      [_op("fusion"), 20, 10], [_op("copy"), 30, 10],
                                      [_op("fusion"), 60, 20]]},
        "paths": {"/device:TPU:0": [
            "jit(s)/jvp()/moe_route/top_k", "jit(s)/transpose(jvp())/moe_route/mul",
            "jit(s)/transpose(jvp())/attention/kernel_bwd_attention/dot_general", "",
            "jit(s)/optimizer/add"]},
        "spans": [["chipbench.window", 0, 100], ["chipbench.train_chunk", 0, 100]],
        "program_spans": [["repro.train.dispatch", 30, 5], ["repro.train.sync", 35, 20]],
    }
    r = scopes.reduce(tr, 1)
    assert r["device_by_scope"] == pytest.approx(
        {"moe_route": 10e-9, "moe_route.bwd": 10e-9, "kernel_bwd_attention": 10e-9,
         "unscoped": 10e-9, "optimizer": 20e-9})
    assert r["breakdown"]["device_scopes"][0] == ["optimizer", pytest.approx(20e-9)]
    assert r["idle_by_span"] == pytest.approx({"train.sync": 20e-9, "train_chunk": 20e-9})
    assert sorted(g[0] for g in r["breakdown"]["idle_gaps"]) == ["train.sync", "train_chunk"]
    # the harness's own reduction still labels both gaps by its span
    assert trace.reduce(tr, 1)["idle_by_span"] == pytest.approx({"train_chunk": 40e-9})
    assert scopes.scope_share(r, ("moe_route", "moe_dispatch", "moe_combine")) == \
        pytest.approx(20.0)
    assert scopes.kernel_bwd_share(r) == pytest.approx(10.0)
    assert scopes.scope_share(r, ("cache_commit",)) is None
    # the sync span [35, 55] closes 15 ns after the last op that began before
    # it closed (30-40)
    assert scopes.sync_lags(tr) == [(True, 15)]


def test_queue_wait_by_hand():
    reqs = [SimpleNamespace(submitted_s=0.0, admitted_s=a) for a in (0.5, 1.0, 2.0, 5.0)]
    reqs.append(SimpleNamespace(submitted_s=0.0, admitted_s=None))
    assert scopes.queue_wait_p95_ms(reqs, 0.75, 3.0) == pytest.approx(1950.0)
    assert scopes.queue_wait_p95_ms(reqs, 6.0, 7.0) is None


def test_paths_by_module_run_and_called_computation():
    """Two programs both hold ``%fusion.1``; the module run that holds each
    event picks the program.  ``%fusion.2`` has no metadata of its own and
    takes its called computation's root's; ``%copy.3`` has none at all."""
    a = ('HloModule jit_a, is_scheduled=true\n\nENTRY %main (p: f32[8]) -> f32[8] {\n'
         '  ROOT %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%c, '
         'metadata={op_name="jit(a)/mlp/add"}\n}\n')
    b = ('HloModule jit_b, is_scheduled=true\n\n%fused_computation.7 (q: f32[8]) -> f32[8] {\n'
         '  %mul.1 = f32[8]{0} multiply(%q, %q)\n'
         '  ROOT %add.2 = f32[8]{0} add(%mul.1, %q), metadata={op_name="jit(b)/loss/add"}\n}\n\n'
         'ENTRY %main (q: f32[8]) -> f32[8] {\n'
         '  %fusion.1 = f32[8]{0} fusion(%q), kind=kLoop, calls=%c2, '
         'metadata={op_name="jit(b)/optimizer/mul"}\n'
         '  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.7\n'
         '  ROOT %copy.3 = f32[8]{0} copy(%fusion.2)\n}\n')
    ev = ["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
          "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %q), kind=kLoop",
          "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %fusion.1), kind=kLoop",
          "%copy.3 = f32[8]{0} copy(f32[8]{0} %fusion.2)", "%absent = f32[] constant(0)"]
    devices = {"/device:TPU:0": [[ev[0], 10, 5], [ev[1], 110, 5], [ev[2], 120, 5],
                                 [ev[3], 130, 5], [ev[4], 140, 5]]}
    runs = {"/device:TPU:0": [["jit_a(123)", 0, 50], ["jit_b(456)", 100, 50]]}
    got = scopes.scope_paths(devices, runs, [a, b])["/device:TPU:0"]
    assert got == ["jit(a)/mlp/add", "jit(b)/optimizer/mul", "jit(b)/loss/add", "", ""]
    # with no module runs the name alone is ambiguous for %fusion.1
    index = scopes.module_index([a, b])
    assert scopes.op_path(index, None, ev[0]) == ""
    assert scopes.op_path(index, None, ev[2]) == "jit(b)/loss/add"


def test_paths_from_programs_compiled_meanwhile():
    """The compiled module's own text, as a device event names each of its
    instructions (metadata left out), finds each scope again."""
    import jax
    import jax.numpy as jnp

    def step(x):
        with jax.named_scope("mlp"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("loss"):
            return jnp.sum(y * 2.0)

    with scopes.compiled_modules() as modules:
        jax.jit(step)(jnp.ones((64, 64))).block_until_ready()
    assert modules
    index = scopes.module_index(modules)
    events = [re.sub(r",? metadata=\{[^}]*\}", "", line.strip())
              for text in modules for line in text.splitlines() if scopes._INSTR.match(line)]
    assert {"mlp", "loss"} <= {scopes.kind(scopes.op_path(index, None, e)) for e in events}


# -- scoped chip traces of the 2-layer cuts ------------------------------------
SCOPED = ["train_2l_scoped", "prefill_2l_scoped", "serve_2l_scoped"]


@pytest.mark.parametrize("name", SCOPED)
def test_scoped_fixture_is_small_and_carries_paths(name):
    assert (DATA / f"{name}.json.gz").stat().st_size < 200_000
    tr = _load(name)
    for dev, evs in tr["devices"].items():
        assert len(tr["paths"][dev]) == len(evs)
    assert any(p for ps in tr["paths"].values() for p in ps)
    # prefill runs neither Trainer.run nor ServingEngine: no program spans
    assert bool(tr["program_spans"]) == (name != "prefill_2l_scoped")


@pytest.mark.parametrize("name", ["train_2l_scoped", "prefill_2l_scoped"])
def test_scopes_account_for_the_busy_time(name):
    r = scopes.reduce(_load(name), 1)
    scoped = r["busy_s"] - r["device_by_scope"].get(scopes.UNSCOPED, 0.0)
    assert scoped >= 0.9 * r["busy_s"], r["breakdown"]["device_scopes"]
    assert sum(r["device_by_scope"].values()) == pytest.approx(r["busy_s"], rel=1e-6)


def test_scoped_fixtures_give_the_layer_readings():
    """What the four per-layer metrics this reduction is for read: finite
    shares of the window, and the engine's queue wait in ms."""
    tra = scopes.reduce(_load("train_2l_scoped"), 1)
    srv = scopes.reduce(_load("serve_2l_scoped"), 1)
    readings = [scopes.scope_share(tra, ("moe_route", "moe_dispatch", "moe_combine")),
                scopes.kernel_bwd_share(tra), scopes.scope_share(srv, ("cache_commit",))]
    for v in readings:
        assert v is not None and math.isfinite(v) and 0 < v < 100, readings
    assert scopes.scope_share(scopes.reduce(_load("prefill_2l_scoped"), 1),
                              ("cache_commit", "loss", "optimizer")) is None
    # the engine's request times: a wait is the time between two host clocks
    reqs = [SimpleNamespace(submitted_s=1.0, admitted_s=1.0 + w) for w in (0.01, 0.02, 0.5)]
    assert 0 < scopes.queue_wait_p95_ms(reqs, 0.0, 2.0) < 1e3


def test_program_spans_label_the_idle_gaps():
    """In the scoped traces the gaps fall in the program's own spans, not
    only the harness's ``train_chunk`` or ``engine_step``."""
    tra = scopes.reduce(_load("train_2l_scoped"), 1)
    srv = scopes.reduce(_load("serve_2l_scoped"), 1)
    assert any(k.startswith("train.") for k in tra["idle_by_span"]), tra["idle_by_span"]
    assert any(k.startswith("serve.") for k in srv["idle_by_span"]), srv["idle_by_span"]
    assert trace.reduce(_load("train_2l_scoped"), 1)["idle_by_span"].keys() <= {
        "train_chunk", "outside spans"}


def test_train_sync_closes_after_the_step_on_one_clock():
    """On one clock a wait for the step cannot close before the step's last
    op ends; it closes a few ms after it (PERF.md gives the distribution)."""
    lags = scopes.sync_lags(_load("train_2l_scoped"))
    assert lags and all(inside and 0 <= lag < 5e6 for inside, lag in lags), lags


def test_serve_feeds_nest_in_admissions_on_the_chip():
    tr = _load("serve_2l_scoped")
    spans = tr["program_spans"]
    feeds = [s for s in spans if s[0] == "repro.serve.feed"]
    admits = [s for s in spans if s[0] == "repro.serve.admit"]
    assert feeds and all(any(a[1] <= f[1] and f[1] + f[2] <= a[1] + a[2] for a in admits)
                         for f in feeds)
