"""Device time by layer kind, and host gaps by the program's own spans.

This extends ``trace.py`` and leaves it as it is: ``load`` returns what
``trace.load`` returns, and keeps besides

* ``paths``: per device, beside each event of its "XLA Ops" line, the scope
  path of the HLO instruction it ran, e.g.
  ``jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/moe_dispatch/sub``.
  The trace's events carry no such path (a TPU op event's stats are its
  device offset and duration), so it is the ``op_name`` metadata of the
  instruction of that name in the compiled program whose run holds the
  event, among those ``compiled_modules`` collected while the traced code
  ran;
* ``program_spans``: the program's host spans (``repro.<name>``, from
  ``repro/runtime/tracing.py``).

``reduce`` returns ``trace.reduce``'s dict, with its idle gaps labelled by
the innermost host span of either prefix, and adds ``device_by_scope``
(seconds per layer kind, averaged over the cell's chips) and the breakdown
entry ``device_scopes`` (its top ten).  A trace with no program spans and
no scopes reduces to ``trace.reduce``'s own numbers and labels.

An op goes under the innermost layer-kind name on its path; under
``<kind>.bwd`` where the path holds ``transpose(`` (the backward, its
recomputed forward included); under ``kernel_bwd_<kernel>`` for a Pallas
kernel's oracle-VJP backward; else under ``unscoped``.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
from collections import defaultdict

import numpy as np

from chipbench import trace

PROGRAM_PREFIX = "repro."
# the layer kinds of repro.runtime.tracing.LAYER_SCOPES: the benchmark keeps
# its own copy of the names it reads
LAYER_KINDS = ("embed", "norm", "attention", "kv_write", "mlp", "moe_route",
               "moe_dispatch", "moe_experts", "moe_combine", "logits", "loss",
               "optimizer", "cache_commit", "sample")
KERNEL_BWD = "kernel_bwd_"
UNSCOPED = "unscoped"
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTR = re.compile(r"^\s*(?:ROOT )?%([^ ]+) = (\S+)")  # name, result type
_CALLS = re.compile(r"(?:calls|to_apply|body)=%([A-Za-z0-9_.\-]+)")
_WRAPPED = re.compile(r"^[A-Za-z0-9_.\-]*\((.*)\)$")


@contextlib.contextmanager
def compiled_modules():
    """Collects the optimized HLO text, metadata included, of every program
    JAX compiles or loads from its persistent cache meanwhile.  (It wraps
    JAX's one compile entry point, ``jax._src.compiler.compile_or_get_cached``;
    nothing runs there inside a measured window.)"""
    from jax._src import compiler

    texts: list = []
    compile_ = compiler.compile_or_get_cached

    def watched(*args, **kwargs):
        exe = compile_(*args, **kwargs)
        texts.extend(m.to_string() for m in exe.hlo_modules())
        return exe

    compiler.compile_or_get_cached = watched
    try:
        yield texts
    finally:
        compiler.compile_or_get_cached = compile_


def _module_paths(text: str) -> dict:
    """instruction name -> (result type, scope path) in one module's text.
    An instruction XLA made without metadata (most fusions) takes the path
    of the computation it calls: its root's, else its first instruction's
    that has one."""
    instrs, members, comp = {}, defaultdict(list), None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.endswith("{"):  # a computation
            comp = line.split()[1 if line.startswith("ENTRY") else 0].lstrip("%")
            continue
        m = _INSTR.match(line)
        if m:
            op, call = _OP_NAME.search(line), _CALLS.search(line)
            instrs[m.group(1)] = (m.group(2), op.group(1) if op else "",
                                  call.group(1) if call else None)
            if line.lstrip().startswith("ROOT"):
                members[comp].insert(0, m.group(1))
            else:
                members[comp].append(m.group(1))
    paths: dict = {}

    def path(name):
        if name not in paths:
            _, op, call = instrs[name]
            paths[name] = op  # (a call never reaches back to its caller)
            inner = iter(members.get(call, []) if not op else ())
            paths[name] = op or next(filter(None, map(path, inner)), "")
        return paths[name]

    return {n: (t, path(n)) for n, (t, _, _) in instrs.items()}


def module_index(modules) -> dict:
    """module name -> [``_module_paths`` of each module text of that name]."""
    out = defaultdict(list)
    for text in modules:
        out[text.split(None, 2)[1].rstrip(",")].append(_module_paths(text))
    return out


def op_path(index: dict, module, text: str) -> str:
    """The scope path of the instruction a trace event names (its HLO text),
    in the modules called ``module`` (in all, where that is None); "" where
    none has it or they disagree on it."""
    m = _INSTR.match(text)
    if not m:
        return ""
    found = [mod[m.group(1)] for mod in (index.get(module) if module in index else
                                         [x for mods in index.values() for x in mods])
             if m.group(1) in mod]
    paths = {p for _, p in found}
    if len(paths) > 1:  # one name in several modules: match the result type
        paths = {p for t, p in found if t == m.group(2)}
    return paths.pop() if len(paths) == 1 else ""


def scope_paths(devices: dict, module_runs: dict, modules) -> dict:
    """Per device, the scope path of each op event: looked up in the module
    whose run (the device's "XLA Modules" line) holds the event."""
    index, out = module_index(modules), {}
    for dev, evs in devices.items():
        runs = sorted(module_runs.get(dev, []), key=lambda r: r[1])
        starts = [s for _, s, _ in runs]
        seen: dict = {}  # one lookup per (module, HLO text)
        out[dev] = []
        for text, start, _ in evs:
            k = bisect.bisect_right(starts, start) - 1
            module = (runs[k][0].split("(")[0]
                      if k >= 0 and start <= runs[k][1] + runs[k][2] else None)
            key = (module, text)
            out[dev].append(seen[key] if key in seen else
                            seen.setdefault(key, op_path(index, module, text)))
    return out


def load(trace_dir: str, modules=()) -> dict:
    """``trace.load``'s dict plus ``paths`` (from ``modules``, the texts
    ``compiled_modules`` collected) and ``program_spans``."""
    import jax

    out = trace.load(trace_dir)
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(max(files, key=os.path.getmtime))
    runs, out["program_spans"] = {}, []
    for plane in pd.planes:
        if plane.name in out["devices"]:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    runs[plane.name] = [[e.name, e.start_ns, e.duration_ns]
                                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out["program_spans"] += [[e.name, e.start_ns, e.duration_ns]
                                         for e in line.events
                                         if e.name.startswith(PROGRAM_PREFIX)]
    out["paths"] = scope_paths(out["devices"], runs, modules)
    return out


def _names(component: str) -> list:
    """The names a path component carries, outermost first:
    ``transpose(jvp(moe_route))`` -> transpose, jvp, moe_route."""
    out = []
    while True:
        m = _WRAPPED.match(component)
        if not m:
            return out + [component]
        out.append(component[: component.index("(")])
        component = m.group(1)


def kind(path: str) -> str:
    """The layer kind an op's scope path puts it under."""
    names = [n for c in path.split("/") for n in _names(c)]
    for n in reversed(names):
        if n.startswith(KERNEL_BWD):
            return n
        if n in LAYER_KINDS:
            return n + ".bwd" if "transpose(" in path else n
    return UNSCOPED


def _label(spans: list, t: float) -> str:
    """The innermost host span of either prefix open at time ``t``."""
    best = None
    for name, s, d in spans:
        if s <= t <= s + d and name != trace.WINDOW_SPAN and (best is None or d < best[1]):
            best = (name, d)
    if best is None:
        return "outside spans"
    name = best[0]
    for prefix in (trace.SPAN_PREFIX, PROGRAM_PREFIX):
        if name.startswith(prefix):
            return name[len(prefix):]
    return name


def _window(tr: dict):
    win = next(s for s in tr["spans"] if s[0] == trace.WINDOW_SPAN)
    return win[1], win[1] + win[2]


def _devices(tr: dict, chips: int) -> list:
    return sorted(tr["devices"], key=lambda n: int(n.rsplit(":", 1)[1]))[:chips]


def _leaves(tr: dict, dev: str, lo: float, hi: float) -> list:
    """(event index, start, end) of the device's leaf ops, clipped to the
    window: the ops ``trace.reduce`` counts."""
    evs = tr["devices"][dev]
    return [(i, max(s, lo), min(s + d, hi))
            for i, s, d in trace.leaves([[i, s, d] for i, (_, s, d) in enumerate(evs)])
            if s < hi and s + d > lo]


def reduce(tr: dict, chips: int) -> dict:
    """``trace.reduce`` over ``load``'s dict, with gaps labelled by either
    prefix's spans, plus device time by layer kind."""
    out = trace.reduce(tr, chips)
    lo, hi = _window(tr)
    devices = _devices(tr, chips)
    by_scope = defaultdict(float)
    gaps = []
    for dev in devices:
        ops = _leaves(tr, dev, lo, hi)
        paths = tr.get("paths", {}).get(dev) or [""] * len(tr["devices"][dev])
        for i, s, e in ops:
            by_scope[kind(paths[i])] += (e - s) * 1e-9 / len(devices)
        if dev == devices[0]:
            ivs = trace.union([(s, e) for _, s, e in ops])
            edges = [lo] + [x for iv in ivs for x in iv] + [hi]
            gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    spans = tr["spans"] + tr.get("program_spans", [])
    by_label = defaultdict(float)
    for s, e in gaps:
        by_label[_label(spans, (s + e) / 2)] += (e - s) * 1e-9
    top_gaps = sorted(gaps, key=lambda g: g[1] - g[0], reverse=True)[:10]
    out["idle_by_span"] = dict(by_label)
    out["breakdown"]["idle_gaps"] = [[_label(spans, (s + e) / 2), (e - s) * 1e-9]
                                     for s, e in top_gaps]
    out["device_by_scope"] = dict(by_scope)
    out["breakdown"]["device_scopes"] = [
        [k, t] for k, t in sorted(by_scope.items(), key=lambda kv: -kv[1])[:10]]
    return out


# -- the readings a per-layer metric would take -------------------------------
def scope_share(red: dict, kinds) -> float | None:
    """Percent of the window spent under the layer kinds ``kinds``, forward
    and backward; None where no op ran under any of them."""
    by = red.get("device_by_scope") or {}
    hits = [t for k, t in by.items() if k.split(".")[0] in kinds]
    return 100.0 * sum(hits) / red["window_s"] if hits else None


def kernel_bwd_share(red: dict) -> float | None:
    """Percent of the window spent in the Pallas kernels' oracle-VJP
    backwards (``kernel_bwd_*``); None where none ran."""
    by = red.get("device_by_scope") or {}
    hits = [t for k, t in by.items() if k.startswith(KERNEL_BWD)]
    return 100.0 * sum(hits) / red["window_s"] if hits else None


def queue_wait_p95_ms(requests, t0: float, t1: float) -> float | None:
    """95th percentile of ``admitted_s - submitted_s`` over the requests
    admitted in the host-clock window (t0, t1]; None where the requests
    carry no such times or none was admitted then."""
    waits = [(q.admitted_s - q.submitted_s) * 1e3 for q in requests
             if getattr(q, "admitted_s", None) is not None
             and getattr(q, "submitted_s", None) is not None and t0 < q.admitted_s <= t1]
    return float(np.percentile(waits, 95)) if waits else None


def sync_lags(tr: dict, span: str = "repro.train.sync") -> list:
    """For each ``span``, the last device op that started before it closed:
    [(whether that op ended inside the span, ns from the op's end to the
    span's end)].  On a shared clock a host wait's span closes just after
    the work it waited for."""
    dev = _devices(tr, 1)[0]
    lo, hi = _window(tr)
    ends = sorted((s, e) for _, s, e in _leaves(tr, dev, lo, hi))
    starts = [s for s, _ in ends]
    run_end = np.maximum.accumulate([e for _, e in ends]) if ends else []
    out = []
    for name, s, d in tr.get("program_spans", []):
        if name != span or not (lo <= s and s + d <= hi):
            continue
        n = int(np.searchsorted(starts, s + d, side="right"))
        if n == 0:
            continue
        t = float(run_end[n - 1])
        out.append((s <= t <= s + d, s + d - t))
    return out
