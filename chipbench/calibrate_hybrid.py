"""Readings the limit of a hybrid prefill cell's ``correct`` is set from, on the chip.

    python3 chipbench/calibrate_hybrid.py --workload <cell> --seconds <s> --seeds <n> [<n> ...] [--controls N]

As ``calibrate.py`` does for the Granite cells: for each seed it runs the
cell as the benchmark does (a short window) and prints, on one JSON line,
the number the program reads.  On the first ``--controls`` seeds (all, if
not given) it also reads the same number with the plain reference computed
another way in the program's place: from float8 inputs (``control``), with
each period's attention layer run first instead of at its offset
(``attn_first``), and with the dt/B/C RMSNorms of the Mamba mixers left out
(``no_ssm_norms``).  The limit lies between the program's readings and the
others'.  The benchmark's own runs never run this.

The readings are kept in ``tests/data/<cell>.readings.jsonl``, which the
tests hold against the committed limit.
"""
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import harness  # noqa: E402

VARIANTS = {"control": {"mode": "fp8"}, "attn_first": {"attn_first": True},
            "no_ssm_norms": {"norms": False}}


def variants(run) -> dict:
    """{name: {number: value}} of the reference computed each other way."""
    from chipbench import weights, weights_jamba
    from chipbench.reference import jamba as ref

    params = weights_jamba.make(run.model, weights.seed_key(run.seed, 0))
    return {name: {"logit_rel_err": ref.variant_rel_err(params, run.sample, run.model, **kw)}
            for name, kw in VARIANTS.items()}


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=None,
                    help="read the control and the faults on the first N seeds only")
    args = ap.parse_args(argv)
    dev = None
    for i, seed in enumerate(args.seeds):
        run = harness.load_run(args.workload, seed, args.seconds, False,
                               T0 if dev is None else time.perf_counter())
        if dev is None:
            dev = harness.require_chips(run.cell["chips"])
            harness.enable_cache()
        res = harness.execute(run, dev)
        t = time.perf_counter()
        extra = variants(run) if args.controls is None or i < args.controls else {}
        rec = {"seed": seed, "program": run.checks, "correct": res["correct"],
               "metrics": res["metrics"], **extra, "control_s": time.perf_counter() - t,
               "memory_peak_bytes": run.memory_peak, "counts": harness.short_counts(run)}
        print(json.dumps(rec, default=str), flush=True)
        run.sample = None
        harness.Run.free()
    return 0


if __name__ == "__main__":
    sys.exit(main())
