#!/usr/bin/env python
"""End-to-end training driver: a ~100M-param granite-family model trained on
the synthetic pipeline with checkpointing and fault-tolerance hooks.

    PYTHONPATH=src python examples/train_100m.py --steps 300 --ckpt DIR  # full run
    PYTHONPATH=src python examples/train_100m.py --steps 20 --ckpt DIR   # quick look

(On the CPU container a step takes seconds; on a real pod the identical step
function runs under the dry-run's production mesh shardings.)
"""
import argparse
import dataclasses
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import get_config  # noqa: E402
from repro.configs.base import InputShape  # noqa: E402
from repro.core.space import SchedulePlan  # noqa: E402
from repro.training import optimizer as optim  # noqa: E402
from repro.training.trainer import Trainer, TrainerConfig  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt", required=True, help="checkpoint directory")
    args = ap.parse_args()

    # ~100M params: granite family, scaled
    cfg = dataclasses.replace(
        get_config("granite-3-2b"),
        name="granite-100m",
        n_layers=12,
        d_model=768,
        n_heads=12,
        n_kv_heads=4,
        head_dim=64,
        d_ff=2048,
        vocab_size=32768,
        dtype="float32",
    )
    print(f"model: {cfg.name}  params={cfg.param_count()/1e6:.1f}M")
    shape = InputShape("train", args.seq, args.batch, "train")
    plan = SchedulePlan(microbatches=2, remat="dots", grad_comm="fp32",
                        opt_dtype="float32")
    tc = TrainerConfig(total_steps=args.steps, ckpt_every=max(args.steps // 4, 10),
                       ckpt_dir=args.ckpt, log_every=10)
    oc = optim.OptimizerConfig(peak_lr=3e-4, warmup_steps=20,
                               total_steps=args.steps)
    trainer = Trainer(cfg, shape, plan, tc, opt_cfg=oc)
    params, _, step = trainer.run()
    for rec in trainer.metrics_log:
        print(f"step {rec['step']:5d}  loss {rec['loss']:.4f}  "
              f"lr {rec['lr']:.2e}  {rec['step_time_s']*1e3:.0f} ms/step")
    print(f"finished at step {step}; checkpoints in {args.ckpt}")

    # demonstrate the failure path: elastic plan from the last checkpoint
    plan2 = trainer.handle_failure([f"h{i}" for i in range(7)],
                                   chips_per_host=4, model_parallel=4)
    print(f"elastic restart plan after losing 1/8 hosts: dp={plan2.data_parallel} "
          f"restart_step={plan2.restart_step}")


if __name__ == "__main__":
    main()
