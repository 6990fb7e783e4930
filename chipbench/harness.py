"""The benchmark's machinery that no cell, configuration or mix names.

``main`` reads ``BENCHMARK.json``, finds the cell, its configuration file
(``configs/<name>.json``), its traffic file (``traffic/<mix>.json``) and its
limits (``limits/<cell>.json``), and hands a :class:`Run` to the driver the
traffic file names (``drivers/<driver>.py``).  The driver sets the run up,
measures inside ``Run.window()``, checks its outputs against the reference
and fills ``Run.e2e``, ``Run.counts`` and ``Run.checks``.  Per-layer metrics
are read by ``metrics/<name>.py`` after the run.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import sys
import time
from typing import Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


class NoChip(RuntimeError):
    pass


def load_module(path: pathlib.Path):
    """Import a file by path (metric files carry dots in their names)."""
    spec = importlib.util.spec_from_file_location(f"chipbench_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Run:
    bench: dict
    cell: dict
    config: dict  # configs/<name>.json
    traffic: dict  # traffic/<mix>.json
    limits: dict  # limits/<cell>.json: {number: limit}
    seed: int
    seconds: float
    trace: bool
    t0: float  # host clock at process start
    e2e: dict = dataclasses.field(default_factory=dict)
    counts: dict = dataclasses.field(default_factory=dict)
    checks: dict = dataclasses.field(default_factory=dict)  # name: value
    attempted: int = 0
    failed: int = 0
    setup_s: Optional[float] = None
    window_s: Optional[float] = None
    memory_peak: Optional[int] = None
    trace_dir: Optional[str] = None
    sample: object = None  # what the check compared (for calibration)
    _ticks: Optional[list] = None  # [window start, last tick, longest gap, its start]

    @property
    def model(self) -> dict:
        return self.config["model"]

    # -- the program's configuration and plan ---------------------------------
    def program_config(self):
        from repro.configs import get_config

        prog = self.config["program"]
        cfg = dataclasses.replace(get_config(prog["arch"]), **prog["overrides"])
        m = self.model
        want = dict(d_model=m["hidden_size"], n_layers=m["num_hidden_layers"],
                    n_heads=m["num_attention_heads"],
                    n_kv_heads=m["num_key_value_heads"],
                    resolved_head_dim=m["head_dim"], d_ff=m["intermediate_size"],
                    vocab_size=m["vocab_size"], rope_theta=m["rope_theta"],
                    n_experts=m.get("num_local_experts", 0) or cfg.n_experts,
                    tie_embeddings=m["tie_word_embeddings"], dtype=m["dtype"])
        if m.get("num_local_experts"):
            want["experts_per_token"] = m["num_experts_per_tok"]
        got = {k: getattr(cfg, k) for k in want}
        if got != want:
            raise ValueError(f"program config {got} is not the file's {want}")
        return cfg

    def tuned_plan(self):
        """The plan ``autotune`` returns for the traffic file's tune cell;
        the host clock around it is the ``tune_s`` count."""
        from repro.core.autotuner import autotune

        t = self.traffic["plan"]["tune"]
        t0 = time.perf_counter()
        with self.span("tune"):
            res = autotune(t["arch"], t["shape"], algo=t["algo"], seed=t["seed"])
        self.counts["tune_s"] = time.perf_counter() - t0
        self.counts["plan"] = res.plan.to_dict()
        return res.plan

    def make_params(self, sharding=None):
        """The seed's weights, in the program's layout, on the device."""
        import jax

        from chipbench import weights

        p = weights.make(self.model, weights.seed_key(self.seed, 0), sharding)
        if not self.counts.get("layout_checked"):
            self._check_layout(p)
        return jax.block_until_ready(p)

    def _check_layout(self, params):
        """The benchmark's weights have the tree, shapes and dtypes of the
        program's own initialiser."""
        import jax

        from repro.models import transformer

        want = jax.eval_shape(lambda k: transformer.init_params(self.program_config(), k),
                              jax.random.PRNGKey(0))
        got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype)
                for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise ValueError("the benchmark's weights do not match the program's layout")
        self.counts["layout_checked"] = True

    def rng(self, *stream: int):
        import numpy as np

        return np.random.default_rng(np.random.SeedSequence([self.seed, 1, *stream]))

    def tokens(self, rng, shape):
        """Token ids drawn as the traffic file says (Zipf over the vocabulary:
        rank r has weight r**-exponent; rank 1 is id 0)."""
        import numpy as np

        t = self.traffic["tokens"]
        V = self.model["vocab_size"]
        if t["dist"] != "zipf":
            raise ValueError(t["dist"])
        w = np.arange(1, V + 1, dtype=np.float64) ** -t["exponent"]
        cdf = np.cumsum(w / w.sum())
        ids = np.searchsorted(cdf, rng.random(shape), side="right")
        return np.minimum(ids, V - 1).astype(np.int32)

    # -- measuring ---------------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str):
        """A host span the device trace can attribute idle gaps to."""
        import jax

        with jax.profiler.TraceAnnotation(f"chipbench.{name}"):
            yield

    def end_setup(self):
        """Set-up ends here.  What it left on the heap is frozen, so that a
        full collection inside the window does not walk it."""
        gc.collect()
        gc.freeze()
        self.setup_s = time.perf_counter() - self.t0

    @contextlib.contextmanager
    def window(self):
        """The measured window.  With ``--trace 1`` the profiler records all
        of it under the span ``chipbench.window`` (the traced window), and
        stops once the window has closed."""
        import jax

        seen = _watch_compiles()
        before = dict(seen)
        pauses = []

        def timed_gc(phase, info, t=[0.0]):
            if phase == "start":
                t[0] = time.perf_counter()
            else:
                pauses.append(time.perf_counter() - t[0])

        gc.callbacks.append(timed_gc)
        span = None
        if self.trace:
            self.trace_dir = str(trace_root() / "trace")
            jax.profiler.start_trace(self.trace_dir)
            span = jax.profiler.TraceAnnotation("chipbench.window")
            span.__enter__()
        t = time.perf_counter()
        self._ticks = [t, t, 0.0, 0.0]
        yield self
        self.window_s = time.perf_counter() - t
        self.tick()
        if span is not None:
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        # the longest stretch between two calls into the program, and when
        # in the window it began: where a slow run lost its time
        self.counts.update(tick_gap_max_s=self._ticks[2], tick_gap_max_at_s=self._ticks[3])
        self._ticks = None
        gc.callbacks.remove(timed_gc)
        self.counts.update(gc_collections=len(pauses), gc_pause_s=sum(pauses),
                           gc_pause_max_s=max(pauses, default=0.0))
        # programs traced or compiled inside the window: there should be none
        self.counts["window_traces"] = seen["trace"] - before["trace"]
        self.counts["window_compiles"] = seen["compile"] - before["compile"]

    def tick(self):
        """Called by the drivers between calls into the program: keeps the
        longest stretch between two calls."""
        if self._ticks is not None:
            now = time.perf_counter()
            t0, last, worst, _ = self._ticks
            if now - last > worst:
                self._ticks[2:] = [now - last, last - t0]
            self._ticks[1] = now

    def read_memory_peak(self):
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in
                 jax.local_devices()[: self.cell["chips"]]]
        self.memory_peak = max((p for p in peaks if p is not None), default=None)

    @staticmethod
    def free():
        import jax

        gc.collect()
        jax.clear_caches()
        gc.collect()


_COMPILES = None


def _watch_compiles() -> dict:
    """Counts of JAX's trace and backend-compile events since the first call."""
    global _COMPILES
    if _COMPILES is None:
        import jax

        _COMPILES = {"trace": 0, "compile": 0}

        def seen(event, duration, **kw):
            if event.endswith("jaxpr_trace_duration"):
                _COMPILES["trace"] += 1
            elif event.endswith("backend_compile_duration"):
                _COMPILES["compile"] += 1

        jax.monitoring.register_event_duration_secs_listener(seen)
    return _COMPILES


def trace_root() -> pathlib.Path:
    """Where a traced run writes its profile: inside the checkout."""
    d = ROOT / ".chipbench_out"
    d.mkdir(exist_ok=True)
    return d


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(chips: int) -> dict:
    dev = device_info()
    if dev["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX's platform is {dev['platform']!r}")
    if dev["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX finds {dev['count']}")
    return dev


def load_run(workload: str, seed: int, seconds: float, trace: bool, t0: float,
             root: pathlib.Path = ROOT) -> Run:
    bench = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = read_json(root / configs[cell["config"]]["file"])
    traffic = read_json(HERE / "traffic" / f"{cell['traffic']}.json")
    limits = read_json(HERE / "limits" / f"{workload}.json")
    return Run(bench, cell, config, traffic, limits, seed, seconds, trace, t0)


def short_counts(run: Run) -> dict:
    """The run's counts without their long per-token lists."""
    return {k: v for k, v in run.counts.items() if not isinstance(v, list) or len(v) <= 16}


def check_line(run: Run) -> dict:
    """{number: {"value", "limit"}} for every number compared."""
    return {k: {"value": v, "limit": run.limits[k]} for k, v in run.checks.items()}


def is_correct(run: Run) -> bool:
    if not run.checks or set(run.checks) != set(run.limits):
        return False
    return all(v is not None and math.isfinite(v) and v <= run.limits[k]
               for k, v in run.checks.items())


def per_layer(run: Run, reduced) -> dict:
    """Each per-layer metric of this cell, read by ``metrics/<name>.py``."""
    from chipbench import flops

    out = {}
    mine = {m["name"] for m in run.bench["end_to_end"]
            if run.cell["name"] in m.get("workloads", [run.cell["name"]])}
    ctx = {"run": run, "trace": reduced, "peaks": flops.peaks(run.cell["chips"])}
    for m in run.bench["per_layer"]:
        cells = m.get("workloads")
        if (run.cell["name"] not in cells) if cells else (m["moves"] not in mine):
            continue
        value = load_module(HERE / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def end_to_end(run: Run) -> dict:
    out = {}
    for m in run.bench["end_to_end"]:
        if run.cell["name"] not in m.get("workloads", [run.cell["name"]]):
            continue
        value = run.setup_s if m["name"] == "setup_s" else run.e2e.get(m["name"])
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def execute(run: Run, dev: dict) -> dict:
    """Drive the cell and return the result line's object."""
    driver = load_module(HERE / "drivers" / f"{run.traffic['driver']}.py")
    driver.run(run)
    reduced = None
    device = dict(dev, memory_peak_bytes=run.memory_peak)
    if run.trace:
        import shutil

        from chipbench import trace

        reduced = trace.reduce(trace.load(run.trace_dir), run.cell["chips"])
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    result = {
        "correct": is_correct(run),
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": per_layer(run, reduced) if run.trace else end_to_end(run),
        "device": device,
    }
    if reduced is not None:
        result["breakdown"] = reduced["breakdown"]
    result["checks"] = check_line(run)
    return result


def enable_cache():
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache`` (the
    program's own default) whatever the environment names, so that a run
    shares its compiled programs with no other checkout; every program in it."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None, t0: Optional[float] = None) -> int:
    import argparse

    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    run = load_run(args.workload, args.seed, args.seconds, bool(args.trace), t0)
    try:
        dev = require_chips(run.cell["chips"])
    except NoChip as e:
        print(f"chipbench: {e}; this benchmark runs only on a TPU", file=sys.stderr)
        return 3
    enable_cache()
    result = execute(run, dev)
    print("counts " + json.dumps(short_counts(run), default=str), file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
