"""Readings the limits of ``correct`` are set from, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed it runs the cell as the benchmark does (a short window) and
prints, on one JSON line, the numbers the program reads (the lower
readings), the numbers the control reads (the reference computed from
float8 inputs put in the program's place: the upper readings) and, for a
training cell, the numbers a planted fault reads (the loss of half the
tokens only).  The benchmark's own runs never run this.

The numbers of each cell's calibration on the chip are kept in
``tests/data/<cell>.readings.jsonl``, which a test holds against the
committed limits.
"""
import json
import os
import sys
import time

T0 = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

from chipbench import harness  # noqa: E402


def control(run, program_only: bool = False) -> dict:
    """The cell's numbers with the control in the program's place, and with
    each planted fault the cell's numbers must catch; for a training cell,
    each side's leaf report.  With ``program_only``, the program's leaf
    report alone."""
    import jax.numpy as jnp

    from chipbench.reference import granite as ref

    kind = run.traffic["driver"]
    drv = harness.load_module(harness.HERE / "drivers" / f"{kind}.py")
    if kind == "train":
        batches, want, g1, change = run.sample
        out = {"leaves": {"program": leaf_report(drv, g1, change, want["grad1_dir"], want)}}
        if program_only:
            return out
        for name, kw in (("control", {"mode": "fp8"}), ("half_batch", {"drop_half": True})):
            got = ref.train(run.make_params(), batches, run.model, run.traffic["optimizer"],
                            against=want["grad1_vec"], **kw)
            out[name] = drv.compare(got["grad1"], got["change"], got["grad1_dir"], want)
            out["leaves"][name] = leaf_report(drv, got["grad1"], got["change"],
                                              got["grad1_dir"], want)
        return out
    if program_only:
        return {}
    if kind == "prefill":
        params = run.make_params()
        tokens = run.sample
        low = jnp.concatenate([ref.seq_logits(params, tokens[b:b + 1], run.model, "fp8")
                               for b in range(tokens.shape[0])])
        return {"control": {"logit_rel_err": ref.logits_rel_err(params, tokens, low,
                                                                run.model)}}
    return {"control": {"served_logit_gap": drv.widest_gap(
        run, run.sample, run.traffic["max_len"], control=True)}}


def leaf_report(drv, g1, change, dirs, want) -> dict:
    """Median and worst leaf of the gradient, direction and change gaps,
    and which leaf is worst."""
    import numpy as np

    out = {}
    for name, got, ref_ in (("grad", g1, want["grad1"]), ("change", change, want["change"]),
                            ("dir", dirs, None)):
        gaps = (drv.leaf_gaps(got, ref_, want["grad1_raw"]) if ref_ is not None
                else {k: got[k] for k in drv.leaf_gaps(g1, want["grad1"], want["grad1_raw"])})
        worst = max(gaps, key=gaps.get)
        out[name] = {"median": float(np.median(list(gaps.values()))), "worst": gaps[worst],
                     "worst_leaf": worst}
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=None,
                    help="read the control and the faults on the first N seeds only; "
                         "the others give the program's numbers (the lower readings)")
    args = ap.parse_args(argv)
    first = True
    for i, seed in enumerate(args.seeds):
        run = harness.load_run(args.workload, seed, args.seconds, False,
                               T0 if first else time.perf_counter())
        if first:
            dev = harness.require_chips(run.cell["chips"])
            harness.enable_cache()
            first = False
        res = harness.execute(run, dev)
        t = time.perf_counter()
        extra = control(run, args.controls is not None and i >= args.controls)
        rec = {"seed": seed, "program": run.checks, "correct": res["correct"],
               "metrics": res["metrics"], **extra,
               "control_s": time.perf_counter() - t,
               "counts": harness.short_counts(run)}
        print(json.dumps(rec, default=str), flush=True)
        run.sample = None
        harness.Run.free()
    return 0


if __name__ == "__main__":
    sys.exit(main())
