"""Prefill of a Jamba hybrid (Mamba-1 + attention) through ``make_prefill_step``.

The loop is ``prefill.py``'s: set-up tunes the plan, makes the weights,
compiles the step for the traffic's (batch, seq_len) and runs it once on
the first prompt batch; the window calls the step on a fresh seeded batch
each time with one call queued behind the one that runs, and closes once
every call it dispatched has returned.  The logits of the window's last
call are kept; once the window has closed the program's weights are freed
and the plain reference (``reference/jamba.py``) computes the same logits
in float32, a block of rows at a time.

What differs from ``prefill.py`` is the model's side: the configuration is
checked against the program's here, the weights come from
``weights_jamba.py`` and are checked against the program's initialiser with
``jax.eval_shape`` here, and the reference is Jamba's.

Traffic keys: batch, seq_len, tokens, plan.
"""
from __future__ import annotations

import dataclasses
import time


def program_config(r):
    """The program's configuration for the file's ``program`` entry, checked
    against the file's model."""
    from repro.configs import get_config

    prog = r.config["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]), **prog["overrides"])
    m = r.model
    want = dict(family="hybrid", d_model=m["hidden_size"], n_layers=m["num_hidden_layers"],
                n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
                resolved_head_dim=m["head_dim"], d_ff=m["intermediate_size"],
                vocab_size=m["vocab_size"], tie_embeddings=m["tie_word_embeddings"],
                dtype=m["dtype"], pos_kind="none", n_experts=m["num_experts"] - 1,
                d_inner=m["mamba_expand"] * m["hidden_size"], ssm_state=m["mamba_d_state"],
                resolved_dt_rank=m["mamba_dt_rank"], conv_width=m["mamba_d_conv"],
                attn_every=m["attn_layer_period"], attn_offset=m["attn_layer_offset"],
                ssm_input_norms=True)
    got = {k: getattr(cfg, k) for k in want}
    if got != want or not m["mamba_conv_bias"] or m["mamba_proj_bias"]:
        raise ValueError(f"program config {got} is not the file's {want}")
    return cfg


def make_params(r, cfg):
    """The seed's weights, checked to have the tree, shapes and dtypes of
    the program's own initialiser."""
    import jax

    from chipbench import weights, weights_jamba
    from repro.models import transformer

    p = weights_jamba.make(r.model, weights.seed_key(r.seed, 0))
    want = jax.eval_shape(lambda k: transformer.init_params(cfg, k), jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), p)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the benchmark's weights do not match the program's layout")
    r.counts["layout_checked"] = True
    return jax.block_until_ready(p)


def run(r) -> None:
    import jax
    import jax.numpy as jnp

    from chipbench import weights, weights_jamba
    from chipbench.reference import jamba as ref
    from repro.configs.base import InputShape
    from repro.training.train_step import make_positions, make_prefill_step

    tr = r.traffic
    B, S = tr["batch"], tr["seq_len"]
    plan = r.tuned_plan()
    cfg = program_config(r)
    params = make_params(r, cfg)
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
    params = weights_jamba.make(r.model, weights.seed_key(r.seed, 0))
    r.checks["logit_rel_err"] = ref.logits_rel_err(params, r.sample, got, r.model)
