"""Training driver: runs the training loop on the devices present.

    python -m repro.launch.train --arch granite-3-2b --smoke --steps 100 \
        --ckpt-dir /path/to/ckpt
    python -m repro.launch.train --arch granite-moe-1b-a400m --seq 4096 \
        --batch 1 --steps 5 --ckpt-dir /path/to/ckpt --autotune mcts_1s

``--smoke`` shrinks the config to ``cfg.reduced()``; without it the
published config runs as it is, and fails if the device cannot hold it.
The run exits non-zero when it takes no step, for example when the
checkpoint directory already holds a run at or past ``--steps``.
"""
from __future__ import annotations

import argparse
import json
import math
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k",
                    help="the cell whose plan is tuned or defaulted")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (cfg.reduced())")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", required=True,
                    help="checkpoint directory; a run resumes from it")
    ap.add_argument("--plan-json", default=None)
    ap.add_argument("--autotune", default=None,
                    help="run this search algo first (e.g. mcts_1s) and train "
                         "with the found schedule")
    args = ap.parse_args(argv)

    from repro.configs import get_config, get_shape
    from repro.configs.base import InputShape
    from repro.core.space import SINGLE_POD, SchedulePlan, ScheduleSpace
    from repro.launch.compile_cache import enable_compile_cache
    from repro.training.trainer import Trainer, TrainerConfig

    enable_compile_cache()
    cfg = get_config(args.arch)
    space = ScheduleSpace(cfg, get_shape(args.shape), SINGLE_POD)
    plan = space.plan_from_actions(space.default_actions())
    if args.autotune:
        from repro.core.autotuner import autotune

        res = autotune(args.arch, args.shape, algo=args.autotune)
        plan = res.plan
        print(f"[train] autotuned plan ({args.autotune}): {plan}")
    if args.plan_json:
        d = plan.to_dict()
        d.update(json.loads(args.plan_json))
        plan = SchedulePlan.from_dict(d)

    if args.smoke:
        cfg = cfg.reduced()
    shape = InputShape("cli", args.seq, args.batch, "train")
    # the plan's one-device fields; its sharding fields need a mesh
    plan = SchedulePlan(
        microbatches=math.gcd(plan.microbatches, args.batch),
        remat=plan.remat,
        attn_block=plan.attn_block,
        scan_chunk=plan.scan_chunk,
        grad_comm="fp32",
        opt_dtype=plan.opt_dtype,
    )
    tc = TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                       ckpt_every=max(args.steps // 2, 1))
    trainer = Trainer(cfg, shape, plan, tc)
    start = trainer.ckpt.latest_step() or 0
    params, opt_state, step = trainer.run()
    for rec in trainer.metrics_log:
        print(f"[train] step={rec['step']:5d} loss={rec['loss']:.4f} "
              f"lr={rec['lr']:.2e} dt={rec['step_time_s']*1e3:.0f}ms")
    if step <= start:
        print(f"[train] took no step: {args.ckpt_dir} already holds step "
              f"{start} of {args.steps}", file=sys.stderr)
        return 1
    print(f"[train] done at step {step} ({step - start} steps taken)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
