"""Reduction of a profiler trace to the device numbers the benchmark reports.

``load`` reads the ``.xplane.pb`` a traced run wrote and keeps what the
reduction needs, in a small JSON-able form: per device, the events of its
"XLA Ops" line (HLO text, start, duration in ns), and the host spans the
harness opened (``chipbench.<name>``).  ``reduce`` turns that into busy
time, idle share, kernel time by name, exposed collective time and idle
gaps labelled by the host span they fall in.  Both clocks are the trace's
own, in nanoseconds.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
               "all-to-all")
_OP = re.compile(r"%([A-Za-z0-9_\-]+?)(?:\.\d+)? = ")
_ARRAY = re.compile(r"\b(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|f64)\[([0-9,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
          "s32": 4, "u32": 4, "f32": 4, "s64": 8, "f64": 8}


def load(trace_dir: str) -> dict:
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = {"devices": {}, "spans": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    texts: dict = {}  # one string per distinct HLO text
                    out["devices"][plane.name] = [
                        [texts.setdefault(e.name, e.name), e.start_ns, e.duration_ns]
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out["spans"] += [[e.name, e.start_ns, e.duration_ns]
                                 for e in line.events if e.name.startswith(SPAN_PREFIX)]
    return out


def op_name(text: str) -> str:
    """The HLO instruction's name without its number: ``moe_gemm`` for
    ``%moe_gemm.58 = bf16[...] custom-call(...)``."""
    m = _OP.match(text)
    return m.group(1) if m else text.split(" ")[0]


def arrays(text: str) -> list:
    """[(dtype, shape)] of the arrays in an HLO instruction's text: its
    result first, then its operands, in order."""
    return [(t, tuple(int(x) for x in dims.split(",") if x))
            for t, dims in _ARRAY.findall(text.split(", custom_call_target")[0])]


def nbytes(dtype: str, shape: tuple) -> int:
    n = _BYTES[dtype]
    for s in shape:
        n *= s
    return n


def leaves(events: list) -> list:
    """Events that contain no other event of the line: the operations that
    run, without the loops and calls that wrap them.  Events of no
    duration (bitcasts, buffer markers) are left out."""
    ev = sorted((e for e in events if e[2] > 0), key=lambda e: (e[1], -e[2]))
    out = []
    for i, (name, start, dur) in enumerate(ev):
        nxt = ev[i + 1] if i + 1 < len(ev) else None
        if nxt is not None and nxt[1] < start + dur and nxt[1] + nxt[2] <= start + dur:
            continue  # it wraps the next event
        out.append((name, start, dur))
    return out


def union(intervals) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out = []
    for s, e in a:
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
        if cur < e:
            out.append((cur, e))
    return out


def _label(spans, t) -> str:
    """The innermost harness span open at time ``t``."""
    best = None
    for name, s, d in spans:
        if s <= t <= s + d and name != WINDOW_SPAN and (best is None or d < best[1]):
            best = (name, d)
    return best[0][len(SPAN_PREFIX):] if best else "outside spans"


def reduce(tr: dict, chips: int) -> dict:
    """Busy and idle time, kernel time, collectives and labelled gaps over
    the window span, averaged over the ``chips`` devices the cell uses."""
    win = [s for s in tr["spans"] if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError("the trace holds no window span")
    lo, hi = win[0][1], win[0][1] + win[0][2]
    devices = sorted(tr["devices"], key=lambda n: int(n.rsplit(":", 1)[1]))[:chips]
    busy, exposed = [], []
    kernels = defaultdict(list)  # op name -> [(text, dur_s)]
    op_time = defaultdict(float)
    gaps = []
    names: dict = {}  # HLO text -> op name, worked out once per text
    for dev in devices:
        ops = []
        for n, s, d in leaves(tr["devices"][dev]):
            if s < hi and s + d > lo:
                name = names.get(n) or names.setdefault(n, op_name(n))
                ops.append((n, name, max(s, lo), min(s + d, hi)))
        ivs = union([(s, e) for _, _, s, e in ops])
        busy.append(length(ivs) * 1e-9)
        coll = union([(s, e) for _, k, s, e in ops if k.startswith(COLLECTIVES)])
        comp = union([(s, e) for _, k, s, e in ops if not k.startswith(COLLECTIVES)])
        exposed.append(length(subtract(coll, comp)) * 1e-9)
        for n, k, s, e in ops:
            kernels[k].append((n, (e - s) * 1e-9))
            op_time[k] += (e - s) * 1e-9 / len(devices)
        if dev == devices[0]:
            edges = [lo] + [x for iv in ivs for x in iv] + [hi]
            gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy) / len(busy)
    by_label = defaultdict(float)
    for s, e in gaps:
        by_label[_label(tr["spans"], (s + e) / 2)] += (e - s) * 1e-9
    top_gaps = sorted(gaps, key=lambda g: g[1] - g[0], reverse=True)[:10]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "collective_exposed_s": sum(exposed) / len(exposed),
        "kernels": dict(kernels),
        "idle_by_span": dict(by_label),
        "breakdown": {
            "device_ops": [[n, t] for n, t in
                           sorted(op_time.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[_label(tr["spans"], (s + e) / 2), (e - s) * 1e-9]
                          for s, e in top_gaps],
        },
    }
