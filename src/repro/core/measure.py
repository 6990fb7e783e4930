"""Real measurement: compile the actual step on the target mesh and derive
roofline terms from the XLA artifact.

This is the paper's "real execution time measurement" (§4.2): expensive
(an XLA compile in a fresh subprocess, seconds) versus the ~100 µs analytic
cost model, and authoritative — FLOPs/bytes come from ``cost_analysis()``
of the compiled SPMD module and collective bytes from parsing the
post-optimization HLO.  The subprocess is required because the production
mesh needs ``xla_force_host_platform_device_count=512``, which must be set
before jax initializes (and must NOT leak into tests/benches).

Conventions (documented in EXPERIMENTS.md):
* ``cost_analysis()`` FLOPs/bytes are per-device for the SPMD program;
  whole-fleet totals multiply by ``chips``.
* collective bytes = Σ operand bytes of all-reduce/all-gather/
  reduce-scatter/all-to-all/collective-permute ops in the per-device HLO.
"""
from __future__ import annotations

import hashlib
import json
import os
import re
import subprocess
import sys
import tempfile
import uuid
from typing import Dict, Optional

from repro.core.cost_model import HW, HardwareSpec
from repro.core.space import SchedulePlan

# v2: the cache key now includes ``devices`` (a pre-fix key collapsed all
# device counts of a cell onto one record) — the versioned subdirectory
# namespaces the corrected entries so a stale pre-fix cache is never served.
CACHE_DIR = os.path.join(
    os.environ.get(
        "REPRO_MEASURE_CACHE",
        os.path.join(os.getcwd(), "experiments", "measure_cache"),
    ),
    "v2",
)

# the subprocess module a measurement spawns; tests point this at
# ``repro.launch.dryrun_stub`` (same CLI, analytic record, no XLA compile)
DRYRUN_MODULE = "repro.launch.dryrun"

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

# op line looks like:
#   %all-gather.74 = f32[2048,128]{1,0} all-gather(%x), channel_id=1,
#       replica_groups=[16,16]<=[16,16]T(1,0), dimensions={0}, ...
# (post-optimization HLO prints operands WITHOUT type annotations, so operand
# bytes are derived from the OUTPUT shape + the op's semantics + group size)
_COLL_LINE_RE = re.compile(
    r"=\s+((?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_V1_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    b = _DTYPE_BYTES.get(dtype)
    if b is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * b


def _group_size(line: str) -> int:
    m = _GROUPS_V2_RE.search(line)
    if m:
        return int(m.group(2))  # [num_groups, group_size]
    m = _GROUPS_V1_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def parse_collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device collective payload from post-SPMD optimized HLO.

    Returns {kind: operand_bytes} plus ``_wire`` (ring wire-byte estimate per
    device) and ``_counts``.  Operand bytes per op:
      all-reduce / all-to-all / collective-permute : output bytes
      all-gather                                   : output / group
      reduce-scatter                               : output × group
    Ring wire bytes per device:
      all-reduce: 2·S·(g-1)/g   all-gather/reduce-scatter: S_full·(g-1)/g
      all-to-all: S·(g-1)/g     collective-permute: S
    """
    out: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    wire = 0.0
    for line in hlo_text.splitlines():
        m = _COLL_LINE_RE.search(line)
        if m is None:
            continue
        shape_str, kind = m.group(1), m.group(2)
        out_bytes = sum(_shape_bytes(t, d) for t, d in _SHAPE_RE.findall(shape_str))
        g = max(_group_size(line), 1)
        if kind == "all-gather":
            operand = out_bytes / g
            wire += out_bytes * (g - 1) / g
        elif kind == "reduce-scatter":
            operand = out_bytes * g
            wire += out_bytes * (g - 1)
        elif kind == "all-reduce":
            operand = out_bytes
            wire += 2 * out_bytes * (g - 1) / g
        elif kind == "all-to-all":
            operand = out_bytes
            wire += out_bytes * (g - 1) / g
        else:  # collective-permute
            operand = out_bytes
            wire += out_bytes
        out[kind] = out.get(kind, 0) + operand
        counts[kind] = counts.get(kind, 0) + 1
    out["_counts"] = counts  # type: ignore[assignment]
    out["_wire"] = wire  # type: ignore[assignment]
    return out


def combine_terms(
    flops_total: float,
    hbm_bytes_total: float,
    coll_bytes_per_chip: float,
    chips: int,
    overlap: float,
    hw: HardwareSpec = HW,
) -> Dict[str, float]:
    compute_s = flops_total / (chips * hw.peak_flops)
    memory_s = hbm_bytes_total / (chips * hw.hbm_bw)
    collective_s = coll_bytes_per_chip / hw.link_bw
    step_s = max(compute_s, memory_s) + (1.0 - overlap) * collective_s
    return {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "step_s": step_s,
    }


# ---------------------------------------------------------------------------
# Subprocess measurement client (with on-disk cache)
# ---------------------------------------------------------------------------
# Cache-key contract (docs/architecture.md §6): the key is a content hash
# of EVERY input that can change the record — key version, arch, shape,
# mesh, DEVICE COUNT, and the full plan dict.  ``devices`` was missing
# before v2: measuring the same (arch, shape, mesh) at a different forced
# device count silently returned the first count's record.
KEY_VERSION = 2


def _cache_key(
    arch: str, shape: str, mesh: str, plan: Optional[dict],
    devices: Optional[int] = None,
) -> str:
    blob = json.dumps(
        [KEY_VERSION, arch, shape, mesh, devices, plan], sort_keys=True
    )
    return hashlib.sha1(blob.encode()).hexdigest()[:20]


def make_request(
    arch: str,
    shape: str,
    mesh: str = "single",
    plan=None,
    devices: Optional[int] = None,
    timeout: float = 1800.0,
    module: Optional[str] = None,
    extras: Optional[dict] = None,
) -> dict:
    """Normalize one measurement request to the plain-dict form every
    measurement path (serial ``measure_cell``, the fleet, the sweep
    harness) shares.  ``extras`` is transport-only — it never enters the
    cache key (fault-injection hooks for tests live there)."""
    if plan is not None and not isinstance(plan, dict):
        plan = plan.to_dict()
    return {
        "arch": arch, "shape": shape, "mesh": mesh, "plan": plan,
        "devices": devices, "timeout": timeout,
        "module": module or DRYRUN_MODULE, "extras": extras,
    }


def request_key(req: dict) -> str:
    return _cache_key(
        req["arch"], req["shape"], req["mesh"], req["plan"],
        req.get("devices"),
    )


def load_record(path: str) -> Optional[dict]:
    """Validated cache read.  A corrupt or truncated entry (a crashed
    writer, a pre-atomic-rename cache) is QUARANTINED — deleted so the
    next call re-measures — instead of being served as a hit or raising
    on every lookup forever."""
    try:
        with open(path) as f:
            rec = json.load(f)
    except FileNotFoundError:
        return None
    except (OSError, ValueError):
        rec = None
    if isinstance(rec, dict) and "step_s" in rec:
        return rec
    try:
        os.remove(path)
    except OSError:
        pass
    return None


def write_record(path: str, record: dict) -> None:
    """Atomic publish: write to a sibling tmp file, ``os.replace`` into
    place.  Readers can never observe a partial record."""
    tmp = f"{path}.tmp.{os.getpid()}.{uuid.uuid4().hex[:8]}"
    try:
        with open(tmp, "w") as f:
            json.dump(record, f, indent=1)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _tail(text, n: int = 2000) -> str:
    return (text or "")[-n:]


def measure_request(req: dict) -> dict:
    """Pure measurement of one request: spawn the dryrun subprocess, point
    its ``--json-out`` at a PRIVATE tmp file, and return the parsed
    record.  No cache interaction and no on-disk residue on any failure
    path — a killed or timed-out compile can never poison a cache entry,
    because the final cache path is only ever written by the caller's
    atomic ``write_record``."""
    arch, shape, mesh = req["arch"], req["shape"], req["mesh"]
    timeout = req.get("timeout") or 1800.0
    tmp = os.path.join(
        tempfile.gettempdir(), f"repro-measure-{os.getpid()}-{uuid.uuid4().hex}.json"
    )
    cmd = [
        sys.executable,
        "-m",
        req.get("module") or DRYRUN_MODULE,
        "--arch", arch,
        "--shape", shape,
        "--mesh", mesh,
        "--json-out", tmp,
    ]
    if req.get("plan") is not None:
        cmd += ["--plan-json", json.dumps(req["plan"])]
    if req.get("devices") is not None:
        cmd += ["--devices", str(req["devices"])]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in [env.get("PYTHONPATH"), _src_path()] if p]
    )
    # the child compiles for host devices and must not claim the chip, which
    # belongs to one process (the dryrun module pins this too)
    env["JAX_PLATFORMS"] = "cpu"
    try:
        try:
            proc = subprocess.run(
                cmd, capture_output=True, text=True, timeout=timeout, env=env
            )
        except subprocess.TimeoutExpired as e:
            # surface the same RuntimeError path as a failed compile, with
            # whatever partial output the subprocess produced
            out = e.stdout.decode() if isinstance(e.stdout, bytes) else e.stdout
            err = e.stderr.decode() if isinstance(e.stderr, bytes) else e.stderr
            raise RuntimeError(
                f"measurement timed out after {timeout:.0f}s for "
                f"{arch}×{shape}×{mesh}:\n"
                f"stdout: {_tail(out)}\nstderr: {_tail(err)}"
            ) from None
        rec = load_record(tmp) if proc.returncode == 0 else None
        if rec is None:
            raise RuntimeError(
                f"measurement failed for {arch}×{shape}×{mesh} "
                f"(exit {proc.returncode}):\n"
                f"stdout: {_tail(proc.stdout)}\nstderr: {_tail(proc.stderr)}"
            )
        return rec
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def measure_cell(
    arch: str,
    shape: str,
    mesh: str = "single",
    plan: Optional[SchedulePlan] = None,
    cache_dir: str = CACHE_DIR,
    timeout: float = 1800.0,
    devices: Optional[int] = None,
    target=None,
) -> dict:
    """Compile (arch, shape, plan) on the target mesh in a subprocess and
    return the measured roofline record.  Results are cached on disk —
    re-measuring a schedule is free, exactly like the paper's compiled-
    binary cache.  Corrupt cache entries are quarantined and re-measured;
    the cache file itself is only ever written atomically.  ``target``
    overrides the measurement function (default: the real subprocess
    ``measure_request``; tests pass an XLA-free stub)."""
    req = make_request(arch, shape, mesh, plan, devices, timeout)
    key = request_key(req)
    os.makedirs(cache_dir, exist_ok=True)
    path = os.path.join(cache_dir, key + ".json")
    rec = load_record(path)
    if rec is not None:
        return rec
    rec = (target or measure_request)(req)
    write_record(path, rec)
    # return the JSON round-trip of what was stored, so a fresh
    # measurement and a later cache hit are structurally identical
    # (e.g. tuples in the plan normalize to lists)
    return load_record(path)


def measured_step_time(
    arch: str, shape: str, mesh: str = "single", plan: Optional[SchedulePlan] = None,
    **kw,
) -> float:
    return measure_cell(arch, shape, mesh, plan, **kw)["step_s"]


def make_measure_fn(arch: str, shape: str, mesh: str = "single", **kw):
    def fn(plan: SchedulePlan) -> float:
        return measured_step_time(arch, shape, mesh, plan, **kw)

    return fn


def _src_path() -> str:
    here = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return here
