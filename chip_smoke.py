"""Run the tuned program once on a TPU, through its normal entry points.

    python chip_smoke.py             # one chip: tune, prefill, serve, train
    python chip_smoke.py --chips 4   # a 2x2 mesh: sharded MoE training only

Each phase prints one JSON line: the device, the config and any cut of it,
compile seconds, smoke timings and the checks it made.  The timings are
smoke timings of one cold run, not benchmark numbers.  When every check
passes, the last line is ``{"ok": true, "device": {...}}``.

The script needs a TPU: with no TPU it exits non-zero and prints no result.
Everything runs in this one process, because a chip belongs to one process.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# Stated tolerances.  The model is bf16 and kernels and oracles round in
# different places, so "the same" means: within the tolerance, or within
# twice the oracle's own error (the oracle at default matmul precision
# against itself at "highest"), whichever is larger.
KERNEL_REL_L2 = 1e-2  # one kernel call vs its oracle at "highest"
PREFILL_REL_L2 = 5e-2  # ||logits - ref|| / ||ref|| after 40 bf16 layers
LOSS_ABS = 2e-2  # |loss - ref loss|, loss ~ ln(vocab) ~ 10.8 at random init
GRAD_REL_L2 = 1e-1  # ||grads - ref grads|| / ||ref grads|| over the whole tree
MESH_LOSS_ABS = 2e-2  # unsharded vs 2x2-sharded first-step loss

# granite-moe depth one v5e chip holds with fp32 Adam state: the train step's
# memory_analysis, compiled for v5e, is 12.3 GB at 8 layers, 14.6 GB at 10
# and 16.9 GB at 12 (arguments + outputs + temporaries; no donation)
TRAIN_LAYERS = 8
TRAIN_STEPS = 4
MESH_CUT_LAYERS = 2

# plan fields the one-chip runs cannot apply: they describe sharding over the
# tuner's pod meshes, and the tuner has no one-chip or 2x2 mesh yet
SHARDING_FIELDS = ("batch_axes", "param_strategy", "mixer_tp", "seq_shard",
                   "ffn_tp", "moe_mode", "vocab_shard", "grad_comm", "overlap")


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _emit(rec: dict) -> dict:
    rec["ok"] = all(rec["checks"].values())
    print(json.dumps(rec), flush=True)
    return rec


def _since(t0: float) -> float:
    return time.perf_counter() - t0


def _rel_l2(a, b) -> float:
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _tree_rel_l2(a, b) -> float:
    import jax
    import jax.numpy as jnp

    def sq(tree):
        return sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                   for x in jax.tree.leaves(tree))

    diff = jax.tree.map(lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
                        a, b)
    return float(jnp.sqrt(sq(diff) / sq(b)))


def _kernel_marker(kernel: str = "flash_attention") -> str:
    """What a compiled program's text holds where the Pallas ``kernel`` ran:
    a Mosaic kernel compiles to a TPU custom call; in interpret mode the
    kernel body is inlined under its own jit name."""
    import jax

    return ("tpu_custom_call" if jax.default_backend() == "tpu"
            else f"jit({kernel})")


def _close(err: float, floor: float, tol: float) -> bool:
    """Within ``tol``, or within twice the oracle's own precision error."""
    return err <= max(tol, 2.0 * floor)


def _three_ways(build, args):
    """Compile and run the function ``build()`` returns three ways: with the
    Pallas kernels (the current kernel mode), under ``kernel_mode("ref")``,
    and under ``kernel_mode("ref")`` at "highest" matmul precision.
    ``build`` is called once per way, so no jit cache is shared between
    them.  Returns {way: (compiled, result, compile_s, run_s)}."""
    import jax

    from repro.kernels.ops import get_kernel_mode, kernel_mode

    out = {}
    for way, mode, precision in (("kernels", get_kernel_mode(), None),
                                 ("ref", "ref", None),
                                 ("ref_highest", "ref", "highest")):
        with kernel_mode(mode), jax.default_matmul_precision(precision):
            t = time.perf_counter()
            compiled = jax.jit(build()).lower(*args).compile()
            compile_s = _since(t)
            t = time.perf_counter()
            res = jax.block_until_ready(compiled(*args))
            out[way] = (compiled, res, compile_s, _since(t))
    return out


def _init_params(cfg, seed: int, **jit_kw):
    import jax

    from repro.models import transformer

    init = jax.jit(transformer.init_params, static_argnums=0, **jit_kw)
    return init(cfg, jax.random.PRNGKey(seed))


def _train_plan(tuned, batch: int):
    """The tuned plan's one-chip fields: tiles and remat as tuned,
    microbatches cut to divide the batch, fp32 Adam moments."""
    from repro.core.space import SchedulePlan

    return SchedulePlan(attn_block=tuned.attn_block, scan_chunk=tuned.scan_chunk,
                        remat=tuned.remat,
                        microbatches=math.gcd(tuned.microbatches, batch),
                        opt_dtype="float32")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def phase_tune():
    """Tune the cells whose plans the one-chip phases run; returns
    (record, plans)."""
    from repro.core.autotuner import autotune

    cells = {"prefill": ("granite-3-2b", "train_4k"),
             "serve": ("granite-3-2b", "decode_32k"),
             "train": ("granite-moe-1b-a400m", "train_4k")}
    plans, timing, applied = {}, {}, {}
    for use, (arch, shape) in cells.items():
        t0 = time.perf_counter()
        plans[use] = autotune(arch, shape, algo="mcts_1s").plan
        timing[f"{arch}x{shape}"] = _since(t0)
    p = plans["prefill"]
    applied["prefill"] = {"tiles": {"attn_block": list(p.attn_block)},
                          "remat": "not applied (inference)",
                          "microbatches": "not applied (batch 1)",
                          "opt_dtype": "not applied (inference)"}
    applied["serve"] = {"tiles": {"attn_block": list(plans["serve"].attn_block)},
                        "remat": "not applied (inference)",
                        "microbatches": "not applied (decode)",
                        "opt_dtype": "not applied (inference)",
                        "kv_dtype": "not applied (engine cache is bf16)"}
    t = plans["train"]
    tp = _train_plan(t, batch=1)
    applied["train"] = {"tiles": {"attn_block": list(tp.attn_block)},
                        "remat": tp.remat,
                        "microbatches": f"{tp.microbatches} (plan says "
                                        f"{t.microbatches}; batch is 1)",
                        "opt_dtype": f"float32 (plan says {t.opt_dtype}; the "
                                     "depth cut is sized for fp32 Adam state)"}
    rec = {
        "phase": "tune", "device": device_info(), "algo": "mcts_1s",
        "cells": {use: f"{a}x{s}" for use, (a, s) in cells.items()},
        "plans": {use: pl.to_dict() for use, pl in plans.items()},
        "applied": applied,
        "not_applied": {"fields": list(SHARDING_FIELDS),
                        "why": "sharding over the tuner's pod meshes; no "
                               "one-chip mesh in the schedule space yet"},
        "smoke_timing_s": timing,
        "checks": {"plans_found": len(plans) == len(cells)},
    }
    return _emit(rec), plans


# kernel shapes at the widths the served configs use: granite-3-2b attention
# and norm, granite-moe-1b-a400m expert GEMM, falcon-mamba-7b scan
KERNEL_CASES = {"attention": dict(B=1, H=32, Hkv=8, S=4096, D=64),
                "rmsnorm": dict(rows=4096, d=2048),
                "moe_gemm": dict(E=32, C=1280, d=1024, f=512),
                "selective_scan": dict(L=4096, Di=8192, N=16)}


def phase_kernels(tiles, cases: dict, seed: int = 0) -> dict:
    """Each Pallas kernel through its ``kernels.ops`` entry point, against
    its oracle at "highest" matmul precision, on bf16 inputs."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    bf = jnp.bfloat16
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def normal(shape, dtype=bf, scale=1.0):
        return (jax.random.normal(next(ks), shape, jnp.float32) * scale).astype(dtype)

    a, r, m, sc = (cases[k] for k in ("attention", "rmsnorm", "moe_gemm",
                                      "selective_scan"))
    calls = {
        "attention": (lambda *x: ops.attention(*x, tiles=tiles), ref.attention,
                      (normal((a["B"], a["H"], a["S"], a["D"])),
                       normal((a["B"], a["Hkv"], a["S"], a["D"])),
                       normal((a["B"], a["Hkv"], a["S"], a["D"])))),
        "rmsnorm": (ops.rmsnorm, ref.rmsnorm,
                    (normal((1, r["rows"], r["d"])), normal((r["d"],)))),
        "moe_gemm": (lambda *x: ops.moe_gemm(*x, tiles=tiles), ref.moe_gemm,
                     (normal((m["E"], m["C"], m["d"])),
                      normal((m["E"], m["d"], m["f"]), scale=m["d"] ** -0.5))),
        "selective_scan": (
            lambda *x: ops.selective_scan(*x, tiles=tiles), ref.selective_scan,
            (normal((1, sc["L"], sc["Di"])),
             jax.nn.softplus(normal((1, sc["L"], sc["Di"]), jnp.float32) - 2).astype(bf),
             -jnp.exp(normal((sc["Di"], sc["N"]), jnp.float32, 0.5)),
             normal((1, sc["L"], sc["N"])), normal((1, sc["L"], sc["N"])),
             normal((sc["Di"],), jnp.float32))),
    }
    errs, compile_s, checks = {}, {}, {}
    for name, (kernel, oracle, args) in calls.items():
        t = time.perf_counter()
        compiled = jax.jit(kernel).lower(*args).compile()
        compile_s[name] = _since(t)
        out = compiled(*args)
        want = jax.jit(oracle)(*args)
        with jax.default_matmul_precision("highest"):
            best = jax.jit(oracle)(*args)
        err, floor = _rel_l2(out, best), _rel_l2(want, best)
        errs[name] = {"rel_l2_vs_oracle_highest": err,
                      "oracle_rel_l2_vs_highest": floor,
                      "shapes": [list(x.shape) for x in args]}
        checks[f"{name}_matches_oracle"] = _close(err, floor, KERNEL_REL_L2)
        checks[f"{name}_kernel_ran"] = _kernel_marker(
            "flash_attention" if name == "attention" else name) in compiled.as_text()
    rec = {"phase": "kernels", "device": device_info(), "errors": errs,
           "tolerance_rel_l2": KERNEL_REL_L2, "compile_s": compile_s,
           "checks": checks}
    return _emit(rec)


def phase_prefill(cfg, plan, *, batch: int, seq: int, seed: int = 0):
    """``make_prefill_step`` with the plan's tiles, against the same step
    under ``kernel_mode("ref")``.  Returns (record, params)."""
    import jax
    import numpy as np

    from repro.configs.base import InputShape
    from repro.training.train_step import make_positions, make_prefill_step

    shape = InputShape("prefill", seq, batch, "prefill")
    t0 = time.perf_counter()
    params = _init_params(cfg, seed)
    jax.block_until_ready(params)
    init_s = _since(t0)
    toks = np.random.default_rng(seed).integers(0, cfg.vocab_size, (batch, seq))
    inputs = {"inputs": jax.numpy.asarray(toks, jax.numpy.int32),
              "positions": make_positions(cfg, batch, seq)}

    runs = _three_ways(lambda: make_prefill_step(cfg, shape, plan), (params, inputs))
    compiled, logits, compile_s, run_s = runs["kernels"]
    ref_logits, ref_hi = runs["ref"][1], runs["ref_highest"][1]
    rel, floor = _rel_l2(logits, ref_logits), _rel_l2(ref_logits, ref_hi)
    agree = float(np.mean(np.asarray(logits.argmax(-1) == ref_logits.argmax(-1))))
    rec = {
        "phase": "prefill", "device": device_info(), "config": cfg.name,
        "layers": cfg.n_layers, "d_model": cfg.d_model, "vocab": cfg.vocab_size,
        "dtype": cfg.dtype, "batch": batch, "seq": seq, "cut": None,
        "attn_block": list(plan.attn_block),
        "compile_s": {w: r[2] for w, r in runs.items()},
        "smoke_timing_s": {"init": init_s, **{w: r[3] for w, r in runs.items()}},
        "logits_rel_l2_vs_ref": rel,
        "logits_rel_l2_vs_ref_highest": _rel_l2(logits, ref_hi),
        "ref_rel_l2_vs_ref_highest": floor, "tolerance_rel_l2": PREFILL_REL_L2,
        "top1_agreement_vs_ref": agree,
        "checks": {
            "kernels_in_step": _kernel_marker() in compiled.as_text(),
            "finite": bool(np.isfinite(np.asarray(logits, np.float32)).all()),
            "shape": tuple(logits.shape) == (batch, seq, cfg.vocab_size),
            "matches_ref": _close(rel, floor, PREFILL_REL_L2),
        },
    }
    return _emit(rec), params


def phase_serve(cfg, params, plan, *, slots: int, max_len: int, n_requests: int,
                prompt_len=(16, 128), new_tokens: int = 16, seed: int = 0) -> dict:
    """Continuous batching through ``ServingEngine``; one request is then
    replayed alone on the same engine and must give the same tokens."""
    import numpy as np

    from repro.serving.engine import ServingEngine

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, int(rng.integers(*prompt_len) + 1))
               for _ in range(n_requests)]
    eng = ServingEngine(cfg, params, batch_slots=slots, max_len=max_len, plan=plan)
    t0 = time.perf_counter()
    uids = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
    done = {r.uid: r for r in eng.run()}
    batched_s = _since(t0)
    t0 = time.perf_counter()
    eng.submit(prompts[-1], max_new_tokens=new_tokens)
    solo = eng.run()
    solo_s = _since(t0)
    last = done.get(uids[-1])
    rec = {
        "phase": "serve", "device": device_info(), "config": cfg.name,
        "layers": cfg.n_layers, "cut": None, "slots": slots, "max_len": max_len,
        "requests": n_requests, "prompt_tokens": [len(p) for p in prompts],
        "new_tokens": new_tokens,
        "compile_s": "not separated (the engine compiles on its first step)",
        "smoke_timing_s": {"batched_run_incl_compile": batched_s, "solo_run": solo_s},
        "checks": {
            "all_completed": sorted(done) == sorted(uids),
            "all_lengths": all(len(r.generated) == new_tokens for r in done.values()),
            "solo_matches_batched": bool(solo) and last is not None
            and solo[0].generated == last.generated,
        },
    }
    return _emit(rec)


def phase_train(cfg, plan, *, batch: int, seq: int, steps: int, seed: int = 0,
                cut: str | None = None) -> dict:
    """``Trainer`` for ``steps`` steps from a fresh checkpoint directory; the
    first step's loss and grads are checked against ``kernel_mode("ref")``."""
    import jax
    import numpy as np

    from repro.configs.base import InputShape
    from repro.training.train_step import make_loss_fn
    from repro.training.trainer import Trainer, TrainerConfig

    shape = InputShape("train", seq, batch, "train")
    with tempfile.TemporaryDirectory() as ckpt_dir:
        tc = TrainerConfig(total_steps=steps, ckpt_every=steps + 1,
                           ckpt_dir=ckpt_dir, log_every=1, seed=seed)
        trainer = Trainer(cfg, shape, plan, tc)
        # the state the trainer starts from: same seed, same arrays
        params, opt_state, _ = trainer.init_state()
        b0 = {k: jax.numpy.asarray(v) for k, v in trainer.pipe.batch_at(0).items()}
        runs = _three_ways(
            lambda: jax.value_and_grad(make_loss_fn(cfg, shape, plan)),
            (params, b0["inputs"], b0["labels"], b0["positions"]))
        (loss, g), (ref_loss, ref_g), (hi_loss, hi_g) = (
            runs[w][1] for w in ("kernels", "ref", "ref_highest"))
        grad_rel, grad_floor = _tree_rel_l2(g, ref_g), _tree_rel_l2(ref_g, hi_g)
        loss_err = abs(float(loss) - float(ref_loss))
        loss_floor = abs(float(ref_loss) - float(hi_loss))
        runs_compile = {w: r[2] for w, r in runs.items()}
        t0 = time.perf_counter()
        # the loop's first call reuses this compile
        mem = trainer.step_fn.lower(params, opt_state, b0).compile().memory_analysis()
        step_compile_s = _since(t0)
        # the loop holds the only live copy of the state, as in a real run
        del g, ref_g, hi_g, runs, params, opt_state
        t0 = time.perf_counter()
        _, _, end = trainer.run()
        run_s = _since(t0)
    losses = [rec["loss"] for rec in trainer.metrics_log]
    stats = jax.devices()[0].memory_stats() or {}
    rec = {
        "phase": "train", "device": device_info(), "config": cfg.name,
        "layers": cfg.n_layers, "d_model": cfg.d_model, "experts": cfg.n_experts,
        "top_k": cfg.experts_per_token, "batch": batch, "seq": seq, "cut": cut,
        "plan": {"attn_block": list(plan.attn_block), "remat": plan.remat,
                 "microbatches": plan.microbatches, "opt_dtype": plan.opt_dtype},
        "compile_s": {"grad": runs_compile, "train_step": step_compile_s},
        "train_step_memory_analysis": {
            k: getattr(mem, f"{k}_size_in_bytes", None)
            for k in ("argument", "output", "alias", "temp", "generated_code")},
        "smoke_timing_s": {"steps": run_s,
                           "step_times": [r["step_time_s"] for r in trainer.metrics_log]},
        "losses": losses, "first_loss": float(loss), "ref_first_loss": float(ref_loss),
        "ref_highest_first_loss": float(hi_loss), "tolerance_loss_abs": LOSS_ABS,
        "grads_rel_l2_vs_ref": grad_rel, "ref_grads_rel_l2_vs_ref_highest": grad_floor,
        "tolerance_grads_rel_l2": GRAD_REL_L2,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_limit": stats.get("bytes_limit"),
        "checks": {
            "steps_taken": end == steps and len(losses) == steps,
            "losses_finite": bool(np.isfinite(losses).all()),
            "loss_matches_ref": _close(loss_err, loss_floor, LOSS_ABS),
            "grads_match_ref": _close(grad_rel, grad_floor, GRAD_REL_L2),
            "first_step_loss_is_checked_loss": bool(losses)
            and abs(losses[0] - float(loss)) <= 1e-3 * abs(float(loss)),
        },
    }
    return _emit(rec)


def phase_mesh(cfg, *, cut_layers: int, batch: int, seq: int, steps: int,
               seed: int = 0, shape2d=(2, 2)) -> dict:
    """Full-depth ``cfg`` trains ``steps`` steps on a (data, model) mesh with
    FSDP+TP and expert-parallel MoE; a ``cut_layers`` cut of it is run
    unsharded on one device and sharded on the mesh, and the first-step
    losses must agree."""
    import jax
    import numpy as np

    from repro.configs.base import InputShape
    from repro.core.space import MeshSpec, SchedulePlan
    from repro.data.pipeline import Pipeline
    from repro.launch.mesh import make_mesh_from_spec
    from repro.models import transformer
    from repro.training import optimizer as optim
    from repro.training.train_step import make_train_step, shardings_for_train

    spec = MeshSpec(("data", "model"), tuple(shape2d))
    mesh = make_mesh_from_spec(spec)
    shape = InputShape("train", seq, batch, "train")
    plan = SchedulePlan(param_strategy="fsdp_tp", moe_mode="ep", remat="dots",
                        microbatches=1, grad_comm="fp32", opt_dtype="float32")
    opt_cfg = optim.OptimizerConfig(total_steps=steps)

    def batch_at(c, step):
        return {k: jax.numpy.asarray(v)
                for k, v in Pipeline(c, shape).batch_at(step).items()}

    def sharded(c):
        """(step, params, opt_state, shardings) for ``c`` on the mesh."""
        abs_params = jax.eval_shape(lambda k: transformer.init_params(c, k),
                                    jax.random.PRNGKey(seed))
        abs_opt = jax.eval_shape(lambda p: optim.init_opt_state(p, opt_cfg),
                                 abs_params)
        p_sh, o_sh, b_sh, _ = shardings_for_train(c, shape, plan, mesh, spec,
                                                  abs_params, abs_opt)
        params = _init_params(c, seed, out_shardings=p_sh)
        opt = jax.jit(lambda p: optim.init_opt_state(p, opt_cfg),
                      out_shardings=o_sh)(params)
        step = jax.jit(make_train_step(c, shape, plan, opt_cfg, mesh, spec),
                       in_shardings=(p_sh, o_sh, b_sh),
                       out_shardings=(p_sh, o_sh, None))
        return step, params, opt, b_sh

    def put(b, b_sh):
        return {k: jax.device_put(v, b_sh[k]) for k, v in b.items()}

    # the full-depth model, sharded
    t0 = time.perf_counter()
    step, params, opt, b_sh = sharded(cfg)
    jax.block_until_ready((params, opt))
    state_bytes = [0] * len(mesh.devices.flat)
    dev_index = {d: i for i, d in enumerate(mesh.devices.flat)}
    for leaf in jax.tree.leaves((params, opt)):
        for s in leaf.addressable_shards:
            state_bytes[dev_index[s.device]] += s.data.nbytes
    mem_after_init = [(d.memory_stats() or {}).get("bytes_in_use")
                      for d in mesh.devices.flat]
    t = time.perf_counter()
    step = step.lower(params, opt, put(batch_at(cfg, 0), b_sh)).compile()
    compile_s = _since(t)
    losses = []
    for i in range(steps):
        params, opt, m = step(params, opt, put(batch_at(cfg, i), b_sh))
        losses.append(float(m["loss"]))
    full_s = _since(t0)
    mem_peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in mesh.devices.flat]
    del params, opt

    # the depth cut, unsharded on one device and sharded on the mesh
    cut = dataclasses.replace(cfg, n_layers=cut_layers,
                              name=f"{cfg.name}-{cut_layers}l")
    b0 = batch_at(cut, 0)
    p1 = _init_params(cut, seed)
    o1 = optim.init_opt_state(p1, opt_cfg)
    one = jax.jit(make_train_step(cut, shape, plan, opt_cfg))
    loss_one = float(one(p1, o1, b0)[2]["loss"])
    del p1, o1
    step_c, pc, oc, bc_sh = sharded(cut)
    loss_mesh = float(step_c(pc, oc, put(b0, bc_sh))[2]["loss"])
    total = sum(state_bytes)
    checks = {
        "kernels_in_step": _kernel_marker() in step.as_text(),
        "losses_finite": bool(np.isfinite(losses).all()),
        "state_spread": min(state_bytes) >= 0.5 * max(state_bytes)
        and max(state_bytes) <= 0.5 * total,
        "mesh_matches_one_device": abs(loss_one - loss_mesh) <= MESH_LOSS_ABS,
    }
    if None not in mem_after_init:  # the backend reports device memory
        checks["device_memory_spread"] = (
            min(mem_after_init) >= 0.5 * max(mem_after_init))
    rec = {
        "phase": "mesh", "device": device_info(), "config": cfg.name,
        "layers": cfg.n_layers, "cut": None, "mesh": dict(zip(spec.names, spec.shape)),
        "plan": {"param_strategy": plan.param_strategy, "moe_mode": plan.moe_mode,
                 "remat": plan.remat},
        "batch": batch, "seq": seq, "losses": losses,
        "compile_s": compile_s,
        "smoke_timing_s": {"full_depth_incl_compile": full_s},
        "state_bytes_per_device": state_bytes,
        "bytes_in_use_per_device_after_init": mem_after_init,
        "peak_bytes_in_use_per_device": mem_peak,
        "compare": {"config": cut.name, "loss_one_device": loss_one,
                    "loss_mesh": loss_mesh, "tolerance_abs": MESH_LOSS_ABS},
        "checks": checks,
    }
    return _emit(rec)


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the 2x2-mesh training phase")
    args = ap.parse_args(argv)

    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is {dev['platform']!r}); "
              "this script runs only on a TPU", file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU devices, "
              f"found {dev['count']}", file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    moe = get_config("granite-moe-1b-a400m")
    if args.chips == 4:
        recs = [phase_mesh(moe, cut_layers=MESH_CUT_LAYERS, batch=2, seq=4096,
                           steps=3)]
    else:
        granite = get_config("granite-3-2b")
        tune, plans = phase_tune()
        from repro.training.train_step import tiles_from_plan

        kernels = phase_kernels(tiles_from_plan(plans["prefill"]), KERNEL_CASES)
        prefill, params = phase_prefill(granite, plans["prefill"], batch=1, seq=4096)
        serve = phase_serve(granite, params, plans["serve"], slots=4, max_len=512,
                            n_requests=8)
        del params
        cut = dataclasses.replace(moe, n_layers=TRAIN_LAYERS,
                                  name=f"{moe.name}-{TRAIN_LAYERS}l")
        train = phase_train(cut, _train_plan(plans["train"], batch=1), batch=1,
                            seq=4096, steps=TRAIN_STEPS,
                            cut=f"n_layers {moe.n_layers} -> {TRAIN_LAYERS}")
        recs = [tune, kernels, prefill, serve, train]
    if not all(r["ok"] for r in recs):
        print("chip_smoke: a phase failed its checks", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device_info()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
