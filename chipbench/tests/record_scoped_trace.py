"""Record the scoped chip traces the layer-kind tests read, and reduce a
whole traced cell by layer kind and program span.

    python3 chipbench/tests/record_scoped_trace.py fixtures <out_dir>
    python3 chipbench/tests/record_scoped_trace.py cell <workload> <seed> <seconds> <out.json>

``fixtures`` runs three short traced windows through the benchmark's own
drivers on the chip, each on a 2-layer cut: training of granite-moe-1b-a400m
(1x1024), prefill of granite-3-2b (1x1024) and serving of granite-3-2b (4
slots x 256, 8 clients).  Each is written in ``scopes.load``'s form (HLO
text cut to its shapes, each event's scope path beside it, taken from the
programs compiled meanwhile), gzipped.

``cell`` runs one cell as ``run.py --trace 1`` does and writes, as JSON:
the result line's numbers, ``scopes.reduce``'s breakdown and
``device_by_scope``, the idle gaps by span, the program spans' durations
by name, the ``train.sync`` clock check and the longest unscoped ops.
"""
import contextlib
import copy
import gzip
import json
import os
import pathlib
import shutil
import sys
import time
from collections import defaultdict

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import harness, scopes, trace  # noqa: E402


def _cut(tr: dict) -> dict:
    for dev, evs in tr["devices"].items():
        tr["devices"][dev] = [[n.split(", custom_call_target")[0][:400], s, d]
                              for n, s, d in evs]
    return tr


def record(workload, layers, traffic_over, seconds, out):
    run = harness.load_run(workload, 7, seconds, True, time.perf_counter())
    run.config = copy.deepcopy(run.config)
    run.config["model"]["num_hidden_layers"] = layers
    run.config["program"]["overrides"]["n_layers"] = layers
    run.traffic.update(traffic_over)
    run.limits = {}
    driver = harness.load_module(harness.HERE / "drivers" / f"{run.traffic['driver']}.py")
    with scopes.compiled_modules() as modules:
        driver.run(run)
    tr = _cut(scopes.load(run.trace_dir, modules))
    shutil.rmtree(run.trace_dir, ignore_errors=True)
    with gzip.open(out, "wt") as f:
        json.dump(tr, f)
    print(out, os.path.getsize(out), {k: len(v) for k, v in tr["devices"].items()},
          len(tr["spans"]), len(tr["program_spans"]), flush=True)


class _Watch:
    """What the serve driver does not keep: the engine's requests, its last
    ``stats()`` and the window's host-clock bounds."""

    def __init__(self):
        self.requests, self.stats, self.window = [], None, None

    @contextlib.contextmanager
    def installed(self):
        from repro.serving import engine

        submit, run_, window = (engine.ServingEngine.submit, engine.ServingEngine.run,
                                harness.Run.window)
        me = self

        def submit_w(self, *a, **k):
            uid = submit(self, *a, **k)
            me.requests.append(self.queue[-1])
            return uid

        def run_w(self, *a, **k):
            done = run_(self, *a, **k)
            me.stats = self.stats()
            return done

        @contextlib.contextmanager
        def window_w(self):
            with window(self) as w:
                t0 = time.perf_counter()
                yield w
            me.window = (t0, time.perf_counter())

        engine.ServingEngine.submit, engine.ServingEngine.run = submit_w, run_w
        harness.Run.window = window_w
        try:
            yield self
        finally:
            engine.ServingEngine.submit, engine.ServingEngine.run = submit, run_
            harness.Run.window = window


def _quantiles(xs):
    import numpy as np

    if not xs:
        return None
    q = np.percentile(xs, [0, 5, 50, 95, 100])
    return {"n": len(xs), "min": q[0], "p5": q[1], "p50": q[2], "p95": q[3], "max": q[4],
            "sum": float(sum(xs))}


def cell(workload, seed, seconds, out):
    run = harness.load_run(workload, seed, seconds, True, time.perf_counter())
    dev = harness.require_chips(run.cell["chips"])
    harness.enable_cache()
    driver = harness.load_module(harness.HERE / "drivers" / f"{run.traffic['driver']}.py")
    with _Watch().installed() as w, scopes.compiled_modules() as modules:
        driver.run(run)
    tr = scopes.load(run.trace_dir, modules)
    shutil.rmtree(run.trace_dir, ignore_errors=True)
    chips = run.cell["chips"]
    base = trace.reduce(tr, chips)
    red = scopes.reduce(tr, chips)
    lo, hi = scopes._window(tr)
    spans = defaultdict(list)
    for name, s, d in tr["program_spans"]:
        if lo <= s and s + d <= hi:
            spans[name].append(d * 1e-6)
    d0 = scopes._devices(tr, 1)[0]
    paths = tr["paths"].get(d0, [])
    unscoped = defaultdict(float)
    for i, s, e in scopes._leaves(tr, d0, lo, hi):
        if scopes.kind(paths[i]) == scopes.UNSCOPED:
            unscoped[trace.op_name(tr["devices"][d0][i][0])] += (e - s) * 1e-9
    lags = scopes.sync_lags(tr)
    res = {
        "workload": workload, "seed": seed, "device": dev,
        "correct": harness.is_correct(run), "checks": harness.check_line(run),
        "e2e": harness.end_to_end(run), "per_layer": harness.per_layer(run, base),
        "setup_s": run.setup_s, "window_s": run.window_s,
        "counts": harness.short_counts(run),
        "base_breakdown": base["breakdown"], "breakdown": red["breakdown"],
        "busy_s": red["busy_s"], "trace_window_s": red["window_s"],
        "idle_by_span": red["idle_by_span"], "device_by_scope": red["device_by_scope"],
        "scoped_share_of_busy": (1 - red["device_by_scope"].get(scopes.UNSCOPED, 0.0)
                                 / red["busy_s"]) if red["busy_s"] else None,
        "program_spans": {k: _quantiles(v) for k, v in sorted(spans.items())},
        "unscoped_ops": sorted(unscoped.items(), key=lambda kv: -kv[1])[:15],
        "paths_found": sum(1 for p in paths if p), "events": len(paths),
        "modules": len(modules),
        "path_sample": sorted(set(paths))[:5] + sorted(set(paths))[-5:],
        "sync_lags_ms": _quantiles([lag * 1e-6 for _, lag in lags]),
        "sync_inside_share": (sum(1 for i, _ in lags if i) / len(lags)) if lags else None,
        "sync_lag_under_1ms_share": (sum(1 for _, lag in lags if 0 <= lag < 1e6) / len(lags))
        if lags else None,
        "engine_stats": w.stats,
        # what the four per-layer metrics this reduction is for would read
        "readings": {
            "moe_route_share.train": scopes.scope_share(
                red, ("moe_route", "moe_dispatch", "moe_combine")),
            "kernel_bwd_share.train": scopes.kernel_bwd_share(red),
            "cache_commit_share.serve": scopes.scope_share(red, ("cache_commit",)),
        },
    }
    if w.requests and w.window:
        res["readings"]["queue_wait_p95_ms.serve"] = scopes.queue_wait_p95_ms(
            w.requests, *w.window)
        res["first_token_ms"] = _quantiles([
            (q.first_token_s - q.admitted_s) * 1e3 for q in w.requests
            if q.first_token_s is not None and w.window[0] < q.admitted_s <= w.window[1]])
    pathlib.Path(out).parent.mkdir(parents=True, exist_ok=True)
    with open(out, "w") as f:
        json.dump(res, f, indent=1, default=str)
    print(json.dumps({k: res[k] for k in ("workload", "correct", "e2e", "setup_s",
                                          "scoped_share_of_busy", "sync_inside_share")},
                     default=str), flush=True)


if __name__ == "__main__":
    if sys.argv[1] == "fixtures":
        d = pathlib.Path(sys.argv[2])
        d.mkdir(parents=True, exist_ok=True)
        harness.require_chips(1)
        harness.enable_cache()
        record("moe_train_4k", 2, {"seq_len": 1024, "chunk_steps": 1}, 0.1,
               d / "train_2l_scoped.json.gz")
        record("dense_prefill_4k", 2, {"batch": 1, "seq_len": 1024}, 0.08,
               d / "prefill_2l_scoped.json.gz")
        record("dense_serve_alpaca", 2, {"slots": 4, "max_len": 256, "clients": 8,
                                         "check_requests": 2}, 0.1,
               d / "serve_2l_scoped.json.gz")
    else:
        cell(sys.argv[2], int(sys.argv[3]), float(sys.argv[4]), sys.argv[5])
