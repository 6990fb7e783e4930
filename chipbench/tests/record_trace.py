"""Record the small chip traces the trace-reduction tests read.

    python3 chipbench/tests/record_trace.py <out_dir>

Runs two short traced windows through the benchmark's own drivers on the
chip: prefill of a 2-layer cut of granite-3-2b (1x1024) and training of a
2-layer cut of granite-moe-1b-a400m (1x1024), and writes each reduced-input
trace (``trace.load``'s form, HLO text cut to its shapes) as gzipped JSON.
"""
import copy
import gzip
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import harness, trace  # noqa: E402


def record(workload, layers, traffic_over, seconds, out):
    run = harness.load_run(workload, 7, seconds, True, time.perf_counter())
    run.config = copy.deepcopy(run.config)
    run.config["model"]["num_hidden_layers"] = layers
    run.config["program"]["overrides"]["n_layers"] = layers
    run.traffic.update(traffic_over)
    run.limits = {}
    driver = harness.load_module(harness.HERE / "drivers" / f"{run.traffic['driver']}.py")
    driver.run(run)
    tr = trace.load(run.trace_dir)
    for dev, evs in tr["devices"].items():
        tr["devices"][dev] = [[n.split(", custom_call_target")[0][:400], s, d]
                              for n, s, d in evs]
    with gzip.open(out, "wt") as f:
        json.dump(tr, f)
    print(out, os.path.getsize(out), {k: len(v) for k, v in tr["devices"].items()},
          len(tr["spans"]), flush=True)


if __name__ == "__main__":
    out = pathlib.Path(sys.argv[1])
    out.mkdir(parents=True, exist_ok=True)
    harness.require_chips(1)
    harness.enable_cache()
    record("dense_prefill_4k", 2, {"batch": 1, "seq_len": 1024}, 0.08,
           out / "prefill_2l.json.gz")
    record("moe_train_4k", 2, {"seq_len": 1024, "chunk_steps": 1}, 0.1,
           out / "train_2l.json.gz")
