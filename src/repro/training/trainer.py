"""Training loop: data pipeline + train step + checkpoints + fault tolerance.

Single-process reference loop (the multi-host deployment wires the same
object to per-host pipelines and the pod coordinator's heartbeat stream —
all decisions below are host-side control-plane logic, identical at fleet
scale).  Each step's host work is a span on a profiler trace (``train.batch``,
``train.dispatch``, ``train.sync``, ``train.log``, ``train.ckpt``;
``runtime/tracing.py``).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint.ckpt import Checkpointer
from repro.configs.base import InputShape, ModelConfig
from repro.core.space import SchedulePlan
from repro.data.pipeline import DataConfig, Pipeline
from repro.models import transformer
from repro.runtime import tracing
from repro.runtime.fault_tolerance import (
    HeartbeatMonitor,
    StragglerPolicy,
    plan_restart,
    rebalance,
)
from repro.training import optimizer as optim
from repro.training.train_step import make_train_step


@dataclass
class TrainerConfig:
    ckpt_dir: str  # a run resumes from the checkpoints it finds here
    total_steps: int = 100
    ckpt_every: int = 50
    ckpt_async: bool = True
    log_every: int = 10
    seed: int = 0


class Trainer:
    def __init__(
        self,
        cfg: ModelConfig,
        shape: InputShape,
        plan: SchedulePlan,
        tc: TrainerConfig,
        opt_cfg: Optional[optim.OptimizerConfig] = None,
        data_cfg: DataConfig = DataConfig(),
        mesh=None,
        mesh_spec=None,
    ):
        self.cfg, self.shape, self.plan, self.tc = cfg, shape, plan, tc
        self.opt_cfg = opt_cfg or optim.OptimizerConfig(
            total_steps=tc.total_steps, moment_dtype=plan.opt_dtype
        )
        self.pipe = Pipeline(cfg, shape, data_cfg)
        self.ckpt = Checkpointer(tc.ckpt_dir)
        self.step_fn = jax.jit(
            make_train_step(cfg, shape, plan, self.opt_cfg, mesh, mesh_spec)
        )
        self.metrics_log: List[Dict] = []
        self.monitor: Optional[HeartbeatMonitor] = None
        self.stragglers = StragglerPolicy()

    # -- state ------------------------------------------------------------------
    def init_state(self):
        params = transformer.init_params(self.cfg, jax.random.PRNGKey(self.tc.seed))
        opt_state = optim.init_opt_state(params, self.opt_cfg)
        return params, opt_state, 0

    def restore_or_init(self):
        params, opt_state, step = self.init_state()
        if self.ckpt.latest_step() is not None:
            params, opt_state, step, _ = self.ckpt.restore(params, opt_state)
        return params, opt_state, step

    # -- loop --------------------------------------------------------------------
    def run(self, params=None, opt_state=None, start_step: Optional[int] = None):
        if params is None:
            params, opt_state, start_step = self.restore_or_init()
        step = start_step or 0
        host = f"host{self.pipe.dc.host_index}"
        while step < self.tc.total_steps:
            t0 = time.perf_counter()
            with tracing.span("train.batch"):
                batch = {
                    k: jnp.asarray(v) for k, v in self.pipe.batch_at(step).items()
                }
            with tracing.span("train.dispatch"):
                params, opt_state, m = self.step_fn(params, opt_state, batch)
            with tracing.span("train.sync"):
                jax.block_until_ready(m)  # honest step timing (async dispatch)
            dt = time.perf_counter() - t0
            self.stragglers.observe(host, dt)
            if self.monitor is not None:
                self.monitor.beat(host)
            step += 1
            if step % self.tc.log_every == 0 or step == 1:
                with tracing.span("train.log"):
                    rec = {
                        "step": step,
                        "loss": float(m["loss"]),
                        "grad_norm": float(m["grad_norm"]),
                        "lr": float(m["lr"]),
                        "step_time_s": dt,
                    }
                    self.metrics_log.append(rec)
            if step % self.tc.ckpt_every == 0:
                with tracing.span("train.ckpt"):
                    self.ckpt.save(
                        step, params, opt_state,
                        extra={"data_step": step},
                        blocking=not self.tc.ckpt_async,
                    )
        self.ckpt.wait()
        return params, opt_state, step

    # -- failure handling (exercised by tests and the fleet coordinator) ---------
    def handle_failure(self, alive_hosts, chips_per_host: int, model_parallel: int):
        """On node loss: build the elastic restart plan from the last
        checkpoint; the data pipeline's stateless indexing makes the
        re-sharded resume exact."""
        latest = self.ckpt.latest_step() or 0
        return plan_restart(
            alive_hosts,
            chips_per_host,
            model_parallel,
            latest,
            self.shape.global_batch,
        )
