"""Prefill through ``make_prefill_step``: long prompts, calls back to back.

Set-up tunes the plan, makes the weights, compiles the step for the
traffic's (batch, seq_len) and runs it once on the first prompt batch.  The
window calls the step on a fresh seeded batch each time, and keeps one
call queued behind the one that runs: the next call is drawn and
dispatched before the host waits for the previous one, so a host pause
shorter than a call leaves the chip busy.  The window closes once every
call it dispatched has returned.  The logits of the window's last call are kept;
once the window has closed the program's weights are freed and the
reference computes the same logits in float32, a row at a time.

Traffic keys: batch, seq_len, tokens, plan.
"""
from __future__ import annotations

import time


def run(r) -> None:
    import jax
    import jax.numpy as jnp

    from chipbench.reference import granite as ref
    from repro.configs.base import InputShape
    from repro.training.train_step import make_positions, make_prefill_step

    tr = r.traffic
    B, S = tr["batch"], tr["seq_len"]
    plan = r.tuned_plan()
    cfg = r.program_config()
    params = r.make_params()
    step = jax.jit(make_prefill_step(cfg, InputShape("prefill", S, B, "prefill"), plan))
    positions = make_positions(cfg, B, S)

    def batch(i):
        return {"inputs": jnp.asarray(r.tokens(r.rng(i), (B, S))), "positions": positions}

    with r.span("prefill_call"):
        jax.block_until_ready(step(params, batch(0)))
    r.end_setup()

    calls, last = 0, None
    with r.window():
        t_end = time.perf_counter() + r.seconds
        while True:
            calls += 1
            with r.span("batch"):
                b = batch(calls)
            with r.span("prefill_call"):
                nxt = (b["inputs"], step(params, b))
            if last is not None:
                with r.span("wait"):
                    jax.block_until_ready(last[1])
                r.tick()
            last = nxt
            if time.perf_counter() >= t_end:
                break
        with r.span("wait"):
            jax.block_until_ready(last[1])
        r.tick()
    r.read_memory_peak()
    del params, step
    r.free()
    r.e2e["prefill_tokens_s"] = calls * B * S / r.window_s
    r.attempted = calls
    r.counts.update(calls=calls, tokens=calls * B * S)

    r.sample, got = last
    r.checks["logit_rel_err"] = ref.logits_rel_err(r.make_params(), r.sample, got, r.model)
