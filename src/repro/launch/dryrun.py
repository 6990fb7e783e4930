import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST come before any other import: jax locks the platform and the host
# device count at first init, and the production meshes (16×16 single-pod,
# 2×16×16 multi-pod) need 512 placeholder CPU devices.  The dry run never
# takes the accelerator: a chip belongs to one process, which may be the
# tuner that started this one.  Never set these globally — smoke tests and
# benches must see 1 device.
"""Multi-pod dry-run driver.

Usage:
    python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh multi --json-out experiments/dryrun_multi.json
    python -m repro.launch.dryrun --arch X --shape Y --plan-json '{"remat": "full", ...}'

Proves, for every (architecture × input-shape) cell, that
``jax.jit(step, in_shardings=..., out_shardings=...).lower(**input_specs)``
compiles on the production mesh; prints ``memory_analysis()`` /
``cost_analysis()`` and writes the roofline record.
"""
import argparse
import json
import sys
import traceback


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", help="architecture id (see repro.configs.ARCH_IDS)")
    ap.add_argument("--shape", help="input shape id (train_4k/prefill_32k/decode_32k/long_500k)")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true", help="run every (arch × shape) cell")
    ap.add_argument("--plan-json", default=None, help="SchedulePlan overrides as JSON")
    ap.add_argument("--json-out", default=None, help="write record(s) to this JSON file")
    ap.add_argument("--devices", type=int, default=None,
                    help="override forced host device count (testing only)")
    args = ap.parse_args(argv)

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}"
        )

    # imports AFTER the flag is pinned
    from repro.configs import cells, get_config, get_shape
    from repro.core.space import SchedulePlan
    from repro.launch.dryrun_impl import evaluate_cell, default_plan
    from repro.launch.mesh import mesh_spec

    plan = None
    if args.plan_json:
        base = json.loads(args.plan_json)
        mspec = mesh_spec(args.mesh == "multi")
        if args.arch and args.shape:
            d = default_plan(get_config(args.arch), get_shape(args.shape), mspec).to_dict()
        else:
            d = SchedulePlan().to_dict()
        d.update(base)
        plan = SchedulePlan.from_dict(d)

    records = []
    failures = []
    if args.all:
        todo = [(c.name, s.name) for c, s in cells()]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        todo = [(args.arch, args.shape)]

    for arch, shape in todo:
        try:
            rec = evaluate_cell(arch, shape, args.mesh, plan)
            records.append(rec)
        except Exception as e:  # noqa: BLE001 - report all failures at end
            traceback.print_exc()
            failures.append((arch, shape, repr(e)))

    if args.json_out:
        out = records[0] if (not args.all and records) else records
        with open(args.json_out, "w") as f:
            json.dump(out, f, indent=1)
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES:")
        for a, s, e in failures:
            print(f"  {a} × {s}: {e}")
        return 1
    print(f"[dryrun] all {len(records)} cell(s) compiled OK on mesh={args.mesh}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
