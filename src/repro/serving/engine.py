"""Serving engine: prefill + batched decode with continuous-batching slots.

The engine keeps a fixed batch of decode slots (static shapes → one compiled
``serve_step``); finished sequences release their slot and the next queued
request is prefix-filled into it.  Mamba/hybrid archs carry conv+SSM state
instead of (or alongside) KV cache — the cache pytree comes from
``transformer.init_cache`` and is opaque here.

A prompt's tokens but the last go into its slot's cache in chunks of
``PREFILL_CHUNK`` (one ``transformer.prefill_chunk`` call each) where the
model has attention mixers and dense MLPs only
(``transformer.prefills_in_chunks``), else one decode call per token; the
last rides the next decode call with the other slots.

What the engine did is readable three ways: ``stats()`` (its call and
admission counts beside ``pending()``), each ``Request``'s host times, and
host spans on a profiler trace (``serve.admit``, ``serve.prefill``,
``serve.feed``, ``serve.decode``, ``serve.sync``, ``serve.bookkeep``;
``runtime/tracing.py``).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.space import SchedulePlan
from repro.models import transformer
from repro.runtime import tracing
from repro.training.train_step import make_serve_step

# prompt tokens a chunk call puts into a slot's cache: a call then reads the
# weights about once (on a v5e at granite-3-2b's widths its GEMMs take less
# time than the weight read), and one width is one compiled program
PREFILL_CHUNK = 128


@dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (prompt_len,) int32 token ids
    max_new_tokens: int
    generated: List[int] = field(default_factory=list)
    done: bool = False
    # host clock (time.perf_counter) when submitted, given a slot, and when
    # its first token reached the host
    submitted_s: Optional[float] = None
    admitted_s: Optional[float] = None
    first_token_s: Optional[float] = None


class ServingEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        batch_slots: int = 4,
        max_len: int = 128,
        plan: Optional[SchedulePlan] = None,
        greedy: bool = True,
        seed: int = 0,
    ):
        assert cfg.input_kind == "tokens", "engine drives token-input archs"
        self.cfg = cfg
        self.params = params
        self.slots = batch_slots
        self.max_len = max_len
        self.plan = plan or SchedulePlan()
        self.greedy = greedy
        self.key = jax.random.PRNGKey(seed)
        self.cache = transformer.init_cache(cfg, batch_slots, max_len)
        self.tokens = np.zeros((batch_slots,), np.int32)
        self.lengths = np.zeros((batch_slots,), np.int32)
        self.active: List[Optional[Request]] = [None] * batch_slots
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        self._uid = 0
        # chunk calls and the prompt tokens they put into caches; decode calls
        # that fed one prompt token / produced a token for every active slot;
        # requests given a slot; slot caches zeroed
        self.counts = {"prefill_calls": 0, "prefill_tokens": 0, "feed_calls": 0,
                       "decode_calls": 0, "admissions": 0, "slot_resets": 0}
        # chunk width, 0 where prompts are fed one token per decode call; a
        # chunk never runs past the cache (see _admit)
        self.chunk = min(PREFILL_CHUNK, max_len) if transformer.prefills_in_chunks(cfg) else 0

        step = make_serve_step(cfg, None, self.plan)

        # the cache is donated to every program: each call updates it in
        # place and the engine keeps only the cache a call returns
        @functools.partial(jax.jit, donate_argnums=(1,))
        def _decode(params, cache, tokens, cur, mask):
            # cur: (B,) per-slot positions — every slot reads/writes its OWN
            # length, so requests of different lengths can share the batch.
            # mask: (B,) bool — only masked slots' cache entries (KV rows,
            # conv/SSM state) are committed; the rest keep their old state,
            # so a prefill feed for one slot can never clobber its
            # neighbours' caches.
            logits, cache = step(params, cache, tokens[:, None], cur, commit=mask)
            with tracing.scope(tracing.SAMPLE):
                next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return next_tok, cache

        @functools.partial(jax.jit, donate_argnums=(0,))
        def _reset_slot(cache, slot):
            # zero one slot's cache state on (re)assignment: stale KV past
            # the new request's length is masked by position anyway, but
            # mamba/hybrid conv+SSM state is NOT position-addressed — a new
            # request must not inherit the previous occupant's state
            return jax.tree_util.tree_map(
                lambda c: c.at[:, slot].set(jnp.zeros_like(c[:, slot])), cache
            )

        @functools.partial(jax.jit, donate_argnums=(1,))
        def _prefill(params, cache, tokens, start, slot):
            return transformer.prefill_chunk(params, cfg, cache, tokens, start, slot)

        self._decode = _decode
        self._reset_slot = _reset_slot
        self._prefill = _prefill

    # -- public API -----------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16) -> int:
        prompt = np.asarray(prompt, np.int32)
        if not 1 <= len(prompt) <= self.max_len:
            raise ValueError(f"a prompt of {len(prompt)} tokens does not fit a slot of "
                             f"{self.max_len} positions")
        self._uid += 1
        self.queue.append(Request(self._uid, prompt, max_new_tokens,
                                  submitted_s=time.perf_counter()))
        return self._uid

    def run(self, max_steps: int = 1000) -> List[Request]:
        """Drive until queue + slots drain (or max_steps).

        Returns THIS call's completions only (not the engine-lifetime
        accumulation) — requests still in flight when ``max_steps``
        exhausts stay active and finish on the next ``run``; check
        ``pending()`` for the still-active/queued counts."""
        n0 = len(self.finished)
        for _ in range(max_steps):
            self._fill_slots()
            if all(r is None for r in self.active):
                break
            self._step()
        return self.finished[n0:]

    def pending(self) -> dict:
        """Requests not yet completed: in-slot actives and queued waiters."""
        return {
            "active": sum(r is not None for r in self.active),
            "queued": len(self.queue),
        }

    def stats(self) -> dict:
        """The engine's counts since it was built, with ``pending()``."""
        return {**self.counts, **self.pending()}

    # -- internals -----------------------------------------------------------------
    def _fill_slots(self):
        for i in range(self.slots):
            if self.active[i] is None and self.queue:
                with tracing.span("serve.admit"):
                    self._admit(i, self.queue.pop(0))

    def _admit(self, i: int, req: Request):
        req.admitted_s = time.perf_counter()
        self.active[i] = req
        self.counts["admissions"] += 1
        self.cache = self._reset_slot(self.cache, i)
        self.counts["slot_resets"] += 1
        fed = req.prompt[:-1]
        if self.chunk:
            C = self.chunk
            for s in range(0, len(fed), C):
                # a last chunk that would run past the cache starts early
                # enough to end at its end, and puts a few rows in again
                start = min(s, self.max_len - C)
                chunk = np.zeros((1, C), np.int32)
                part = fed[start:start + C]
                chunk[0, :len(part)] = part
                # rows past the prompt hold the padding's K/V: decode masks
                # them (t <= cur) and overwrites them as it advances
                with tracing.span("serve.prefill"):
                    self.cache = self._prefill(self.params, self.cache, jnp.asarray(chunk),
                                               jnp.int32(start), jnp.int32(i))
                self.counts["prefill_calls"] += 1
                self.counts["prefill_tokens"] += min(C, len(fed) - s)
            self.lengths[i] = len(fed)
        else:
            self.lengths[i] = 0
            for t in fed:
                self.tokens[i] = t
                with tracing.span("serve.feed"):
                    self._single_feed(i)
        self.tokens[i] = req.prompt[-1]

    def _single_feed(self, slot: int):
        # prefill one token for ONE slot: per-slot positions plus a one-hot
        # commit mask — other slots' KV/state are untouched (pre-fix, this
        # decoded the full batch at the new slot's position and clobbered
        # every active neighbour's cache)
        mask = np.zeros((self.slots,), bool)
        mask[slot] = True
        # copies: nothing waits for this call, and on the CPU an array made
        # from a NumPy buffer may share it, so the call would read the
        # lengths and tokens the next feeds write
        _, self.cache = self._decode(
            self.params, self.cache, jnp.asarray(self.tokens.copy()),
            jnp.asarray(self.lengths.copy()), jnp.asarray(mask),
        )
        self.counts["feed_calls"] += 1
        self.lengths[slot] += 1

    def _step(self):
        # one decode step for every ACTIVE slot at its own position
        # (pre-fix: one shared cur = lengths.max() wrote every slot's KV at
        # the longest slot's position)
        mask = np.array([r is not None for r in self.active], bool)
        with tracing.span("serve.decode"):
            next_tok, self.cache = self._decode(
                self.params, self.cache, jnp.asarray(self.tokens),
                jnp.asarray(self.lengths), jnp.asarray(mask),
            )
        self.counts["decode_calls"] += 1
        with tracing.span("serve.sync"):
            next_np = np.asarray(next_tok)
        with tracing.span("serve.bookkeep"):
            self._bookkeep(next_np)

    def _bookkeep(self, next_np: np.ndarray):
        now = time.perf_counter()
        for i, req in enumerate(self.active):
            if req is None:
                continue
            if not req.generated:
                req.first_token_s = now
            req.generated.append(int(next_np[i]))
            self.tokens[i] = next_np[i]
            self.lengths[i] += 1
            if (
                len(req.generated) >= req.max_new_tokens
                or self.lengths[i] >= self.max_len - 1
            ):
                req.done = True
                self.finished.append(req)
                self.active[i] = None
