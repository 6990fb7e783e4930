"""Serving through ``ServingEngine``: a closed loop of clients with no think
time, driven one engine step at a time (``run(max_steps=1)``).

Request lengths are the same in every run: the traffic file's prompt and
output lengths are quantiles of lognormal distributions, put in an order
fixed by its ``order_seed``, and request i takes the i-th of each (cycling).
The seed draws the token ids.  The engine starts with its slots held by
``slots`` requests whose prompts are ``warm_prompt_len`` long, so set-up
fills the slots quickly; the other clients' requests wait in the queue.
When a request finishes, its client sends the next one.

A token's time is the host clock when the engine step that produced it
returned (the token is then on the host).  Once the window has closed,
the reference runs over a sample of the requests served in the window and
reads how far each served token's logit lies below its best.

Traffic keys: slots, max_len, clients, think_s, prompt_len, output_len,
quantiles, order_seed, warm_prompt_len, tokens, plan, check_requests.
"""
from __future__ import annotations

import time

import numpy as np


def lengths(spec: dict, n: int, order_seed: int) -> np.ndarray:
    """``n`` quantiles of a clipped lognormal, in a fixed shuffled order."""
    from statistics import NormalDist

    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    q = np.clip(np.rint(spec["median"] * np.exp(spec["sigma"] * z)), spec["min"], spec["max"])
    return q.astype(int)[np.random.default_rng(order_seed).permutation(n)]


def schedule(tr: dict):
    """(prompt length, output length) of request i, for every i."""
    n = tr["quantiles"]
    p = lengths(tr["prompt_len"], n, tr["order_seed"])
    o = lengths(tr["output_len"], n, tr["order_seed"] + 1)

    def at(i: int):
        plen = tr["warm_prompt_len"] if i < tr["slots"] else int(p[i % n])
        return plen, int(o[i % n])

    return at


def run(r) -> None:
    from repro.serving.engine import ServingEngine

    tr = r.traffic
    if tr["think_s"]:
        raise ValueError("the closed loop has no think time")
    at = schedule(tr)
    plan = r.tuned_plan()
    cfg = r.program_config()
    eng = ServingEngine(cfg, r.make_params(), batch_slots=tr["slots"],
                        max_len=tr["max_len"], plan=plan, greedy=True)
    reqs = []  # every request sent, in order
    times = {}  # uid -> host time of each of its tokens

    def send():
        i = len(reqs)
        plen, olen = at(i)
        with r.span("submit"):
            eng.submit(r.tokens(r.rng(i), (plen,)), max_new_tokens=olen)
        reqs.append(eng.queue[-1])
        times[reqs[-1].uid] = []

    def step():
        with r.span("engine_step"):
            done = eng.run(max_steps=1)
        now = time.perf_counter()
        for q in reqs[-tr["clients"]:]:  # every request in flight
            new = len(q.generated) - len(times[q.uid])
            times[q.uid] += [now] * new
        for _ in done:
            send()
        return done

    for _ in range(tr["clients"]):
        send()
    step()  # fills every slot and compiles the engine's programs
    r.end_setup()

    finished, steps = [], 0
    with r.window():
        t0 = time.perf_counter()
        t_end = t0 + r.seconds
        while time.perf_counter() < t_end:
            finished += step()
            steps += 1
            r.tick()
        t1 = time.perf_counter()
    r.read_memory_peak()
    del eng
    r.free()

    in_win = lambda t: t0 < t <= t1
    served = sum(1 for q in reqs for t in times[q.uid] if in_win(t))
    gaps = [b - a for q in reqs for a, b in zip(times[q.uid], times[q.uid][1:])
            if in_win(a) and in_win(b)]
    admitted = [q for q in reqs if times[q.uid] and in_win(times[q.uid][0])]
    r.e2e["serve_tokens_s"] = served / r.window_s
    r.attempted = len(admitted)
    r.counts.update(
        tokens=served, requests_finished=len(finished), requests_admitted=len(admitted),
        engine_steps=steps, feed_steps=sum(len(q.prompt) - 1 for q in admitted),
        itl_ms=[g * 1e3 for g in gaps],
        # (context length of each useful token: prompt tokens fed, tokens served)
        fed_contexts=[c for q in admitted for c in range(1, len(q.prompt))],
        served_contexts=[len(q.prompt) + j for q in reqs for j, t in
                         enumerate(times[q.uid]) if in_win(t)])

    inflight = [q for q in reqs if not q.done and q.generated]
    sample = check_sample(r, finished, inflight, tr["check_requests"])
    r.sample = [(q.prompt, list(q.generated)) for q in sample]
    r.counts["checked_tokens"] = sum(len(g) for _, g in r.sample)
    r.checks["served_logit_gap"] = widest_gap(r, r.sample, tr["max_len"])


def check_sample(r, finished: list, inflight: list, n: int) -> list:
    """The longest request finished in the window, the others drawn from the
    seed, and, where fewer than ``n`` finished, requests still in flight at
    the close (their tokens served so far), drawn from the seed."""
    pool = sorted(finished, key=lambda q: len(q.prompt) + len(q.generated), reverse=True)
    rng = r.rng(1 << 20)
    rest = [pool[i] for i in 1 + rng.permutation(len(pool) - 1)] if pool else []
    more = [inflight[i] for i in rng.permutation(len(inflight))]
    return (pool[:1] + rest + more)[:n]


def widest_gap(r, sample: list, max_len: int, control: bool = False):
    """Widest gap, over every served token of ``sample`` ((prompt, served
    tokens) pairs), between the
    reference's best logit and its logit of the served token; with
    ``control``, of the token the fp8 reference puts first instead."""
    import jax.numpy as jnp

    from chipbench.reference import granite as ref

    if not sample:
        return None
    params = r.make_params()
    worst = 0.0
    for prompt, gen in sample:
        seq = np.concatenate([prompt, np.asarray(gen[:-1], np.int32)])
        pad = np.zeros((1, max_len), np.int32)
        pad[0, : len(seq)] = seq
        lg = ref.seq_logits(params, jnp.asarray(pad), r.model)[0]
        first = len(prompt) - 1
        rows = lg[first: first + len(gen)]
        served = jnp.asarray(gen, jnp.int32)
        if control:
            low = ref.seq_logits(params, jnp.asarray(pad), r.model, "fp8")[0]
            served = jnp.argmax(low[first: first + len(gen)], axis=-1)
        gap = ref.served_gaps(rows, served)
        worst = max(worst, float(jnp.max(gap)))
    return worst
