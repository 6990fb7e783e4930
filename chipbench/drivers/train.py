"""Training through ``Trainer.run``, fed token batches drawn from the seed.

Set-up tunes the plan, makes the weights and the fp32 Adam state on the
device, builds one ``Trainer`` and drives it through its first
``checked_steps`` steps with the window's own call and feed; those steps
compile the step and give the numbers the reference checks.  The window
then runs the same trainer in chunks of ``chunk_steps`` until ``--seconds``
have passed.  Once it has closed, the state is freed and the reference
repeats the checked steps from the same seed.

Traffic keys: batch, seq_len, tokens, plan, optimizer, checked_steps,
chunk_steps.
"""
from __future__ import annotations

import math
import tempfile
import time

import numpy as np


class Feed:
    """``Pipeline.batch_at``'s interface over the run's seeded token draw;
    every step's rows differ."""

    def __init__(self, run, batch: int, seq: int):
        from repro.data.pipeline import DataConfig

        self.run, self.batch, self.seq = run, batch, seq
        self.dc = DataConfig()  # one host

    def batch_at(self, step: int, host_index=None) -> dict:
        toks = self.run.tokens(self.run.rng(step), (self.batch, self.seq))
        pos = np.broadcast_to(np.arange(self.seq, dtype=np.int32), (self.batch, self.seq))
        return {"inputs": toks, "labels": toks, "positions": pos.copy()}


def one_chip_plan(tuned, batch: int):
    """The tuned plan's one-chip fields: tiles and remat as tuned,
    microbatches cut to divide the batch, fp32 Adam moments."""
    from repro.core.space import SchedulePlan

    return SchedulePlan(attn_block=tuned.attn_block, scan_chunk=tuned.scan_chunk,
                        remat=tuned.remat, microbatches=math.gcd(tuned.microbatches, batch),
                        opt_dtype="float32")


def run(r) -> None:
    import jax
    import jax.numpy as jnp

    from chipbench import weights
    from chipbench.reference import granite as ref
    from repro.configs.base import InputShape
    from repro.training import optimizer as optim
    from repro.training.trainer import Trainer, TrainerConfig

    tr = r.traffic
    B, S, n_check = tr["batch"], tr["seq_len"], tr["checked_steps"]
    o = tr["optimizer"]
    plan = one_chip_plan(r.tuned_plan(), B)
    r.counts["plan_applied"] = plan.to_dict()
    cfg = r.program_config()
    oc = optim.OptimizerConfig(
        peak_lr=o["peak_lr"], warmup_steps=o["warmup_steps"], total_steps=o["total_steps"],
        b1=o["b1"], b2=o["b2"], eps=o["eps"], weight_decay=o["weight_decay"],
        clip_norm=o["clip_norm"], moment_dtype="float32")
    feed = Feed(r, B, S)
    norms = jax.jit(lambda t: {k: jnp.linalg.norm(v.astype(jnp.float32).ravel())
                               for k, v in weights.flatten(t).items()})
    with tempfile.TemporaryDirectory() as ckpt_dir:
        tc = TrainerConfig(ckpt_dir=ckpt_dir, total_steps=0, ckpt_every=1 << 40,
                           ckpt_async=False, log_every=1)
        trainer = Trainer(cfg, InputShape("train", S, B, "train"), plan, tc, opt_cfg=oc)
        trainer.pipe = feed
        # the trainer takes its state from here, so that no caller holds a
        # second copy of it while the loop runs
        box = []
        trainer.init_state = box.pop
        params = r.make_params()
        box.append((params, jax.jit(lambda p: optim.init_opt_state(p, oc))(params), 0))
        del params

        def advance(n: int) -> int:
            with r.span("train_chunk"):
                trainer.tc.total_steps = box[-1][2] + n
                box.append(trainer.run())
            return box[-1][2]

        advance(1)
        g1 = {k: float(v) / (1 - o["b1"]) for k, v in norms(box[-1][1]["mu"]).items()}
        # the first gradient's direction, kept on the host for the check
        mu1 = {k: np.asarray(v) for k, v in weights.flatten(
            jax.device_get(box[-1][1]["mu"])).items()}
        advance(n_check - 1)
        p0 = r.make_params()
        change = {k: float(v) for k, v in norms(
            jax.tree.map(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                         box[-1][0], p0)).items()}
        del p0
        losses = [rec["loss"] for rec in trainer.metrics_log[:n_check]]
        r.end_setup()

        start = box[-1][2]
        with r.window():
            t_end = time.perf_counter() + r.seconds
            while time.perf_counter() < t_end:
                advance(tr["chunk_steps"])
                r.tick()
        steps = box[-1][2] - start
        step_times = [rec["step_time_s"] for rec in trainer.metrics_log[n_check:]]
        r.read_memory_peak()
        box.clear()
        del trainer
    r.free()
    r.e2e["train_tokens_s"] = steps * B * S / r.window_s
    r.attempted = steps
    r.counts.update(steps=steps, tokens=steps * B * S, losses=losses,
                    step_s_median=float(np.median(step_times)) if step_times else None,
                    step_s_max=max(step_times, default=None))

    batches = [jnp.asarray(feed.batch_at(i)["inputs"]) for i in range(n_check)]
    want = ref.train(r.make_params(), batches, r.model, o, against=mu1, keep=True)
    del mu1
    r.sample = (batches, want, g1, change)
    r.checks.update(compare(g1, change, want["grad1_dir"], want))
    r.counts["reference_losses"] = want["losses"]
    r.counts["loss_gaps"] = [abs(a - b) for a, b in zip(losses, want["losses"])]


def leaf_gaps(got: dict, want: dict, raw: dict) -> dict:
    """Each leaf's |got norm - reference norm| over the larger of the
    reference's norm of that leaf and its median leaf norm.  Leaves whose
    reference gradient is under a thousandth of the median leaf's are left
    out: they move by round-off alone."""
    med_raw = float(np.median(list(raw.values())))
    keys = [k for k in want if raw[k] >= 1e-3 * med_raw]
    med = float(np.median([want[k] for k in keys]))
    return {k: abs(got[k] - want[k]) / max(want[k], med) for k in keys}


def leaf_gap(got: dict, want: dict, raw: dict) -> float:
    """The worst leaf's gap."""
    return max(leaf_gaps(got, want, raw).values())


def dir_gap(dirs: dict, raw: dict) -> float:
    """The median leaf's ``1 - cos`` between the first gradient and the
    reference's, over the leaves ``leaf_gaps`` keeps."""
    med_raw = float(np.median(list(raw.values())))
    return float(np.median([dirs[k] for k in dirs if raw[k] >= 1e-3 * med_raw]))


def compare(g1, change, dirs, want) -> dict:
    """The numbers compared: the first gradient's norms and direction
    (``dirs``: each leaf's ``1 - cos`` against the reference's), and the
    change.  The losses are not compared: the first step's loss gap has no
    control or fault that reads three times it (PERF.md), and the later
    steps' swing from seed to seed."""
    return {
        "grad_gap": leaf_gap(g1, want["grad1"], want["grad1_raw"]),
        "grad_dir_gap": dir_gap(dirs, want["grad1_raw"]),
        "change_gap": leaf_gap(change, want["change"], want["grad1_raw"]),
    }
