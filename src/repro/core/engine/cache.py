"""Shared transposition cache over the scheduling MDP.

The MDP is a deterministic prefix tree: a complete schedule IS its action
tuple, so ``terminal_cost`` is a pure function of the state and
``partial_cost`` a pure function of the prefix.  The reference ensemble
re-prices the same complete schedules thousands of times — every one of the
16 trees re-samples overlapping regions of the space, and tree reuse across
decision rounds revisits the same subtree terminals round after round.
``TranspositionCache`` memoizes both signals once, shared across all trees
and all rounds; ``CachedMDP`` is a drop-in ``ScheduleMDP`` wrapper so every
search backend (MCTS, ArrayMCTS, beam, random) gets the cache for free.

Values are bit-identical to uncached evaluation (a pure memo — no
rounding, no eviction), so search trajectories are unchanged; only the
number of cost-model evaluations drops.
"""
from __future__ import annotations

import itertools
from typing import Dict, List, Optional, Sequence, Tuple

State = Tuple[int, ...]

# incremental-export cursor: (len(terminal), len(partial)) — see
# TranspositionCache.watermark/export_since
Watermark = Tuple[int, int]


class TranspositionCache:
    """Memo of {complete action tuple -> terminal cost} and
    {prefix action tuple -> default-completed partial cost}."""

    __slots__ = ("terminal", "partial", "hits", "misses", "dedup")

    def __init__(self):
        self.terminal: Dict[State, float] = {}
        self.partial: Dict[State, float] = {}
        self.hits = 0
        self.misses = 0
        # subset of ``hits`` served by in-batch deduplication: a state that
        # appeared earlier in the SAME miss batch (priced once, served K
        # times) — the batched engines' structural win over scalar walks
        self.dedup = 0

    # -- stats ---------------------------------------------------------
    @property
    def n_entries(self) -> int:
        return len(self.terminal) + len(self.partial)

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def stats(self) -> dict:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "dedup": self.dedup,
            "hit_rate": self.hit_rate,
            "terminal_entries": len(self.terminal),
            "partial_entries": len(self.partial),
        }

    # -- pickling and merge --------------------------------------------
    def __getstate__(self):
        # a pickled cache (inside a round-boundary checkpoint) carries its
        # entries but fresh counters, so a resumed run's hit/miss counts
        # are its own activity and ``merge`` never double counts
        return {"terminal": self.terminal, "partial": self.partial}

    def __setstate__(self, state):
        self.terminal = state["terminal"]
        self.partial = state["partial"]
        self.hits = 0
        self.misses = 0
        self.dedup = 0

    def merge(self, other: "TranspositionCache") -> None:
        """Fold another cache into this one.  Every entry is the exact
        value of its state, so this is a plain order-independent update."""
        self.terminal.update(other.terminal)
        self.partial.update(other.partial)
        self.hits += other.hits
        self.misses += other.misses
        self.dedup += other.dedup

    # -- incremental export (plan-store cell sync) ---------------------
    # The plan store's cell tier writes only the entries added since its
    # last sync: ``watermark()`` is taken at every sync and
    # ``export_since(wm)`` returns what came after it.  Dicts are
    # insertion-ordered and the tables are append-only (nothing is ever
    # evicted or rewritten), so "everything since" is a pair of islices —
    # O(new entries), never a whole-table diff.

    def watermark(self) -> Watermark:
        """Cursor for ``export_since``: the two table lengths."""
        return (len(self.terminal), len(self.partial))

    def export_since(self, wm: Optional[Watermark]):
        """``(terminal, partial)`` entries added since ``wm`` (all of them
        when ``wm`` is None)."""
        t0, p0 = wm if wm is not None else (0, 0)
        return (dict(itertools.islice(self.terminal.items(), t0, None)),
                dict(itertools.islice(self.partial.items(), p0, None)))

    def apply_export(self, entries) -> None:
        """Fold an ``export_since`` payload into this cache."""
        t, p = entries
        self.terminal.update(t)
        self.partial.update(p)


class CachedMDP:
    """``ScheduleMDP`` wrapper memoizing ``terminal_cost``/``partial_cost``.

    Everything else delegates to the wrapped MDP, so this nests around any
    object implementing the MDP protocol (including test doubles)."""

    def __init__(self, mdp, cache: TranspositionCache = None):
        self.mdp = mdp
        self.cache = cache if cache is not None else TranspositionCache()

    # -- pure structure: straight delegation ---------------------------
    @property
    def initial_state(self) -> State:
        return self.mdp.initial_state

    @property
    def space(self):
        return self.mdp.space

    @property
    def cost_model(self):
        return self.mdp.cost_model

    def n_actions(self, state: State) -> int:
        return self.mdp.n_actions(state)

    def step(self, state: State, action: int) -> State:
        return self.mdp.step(state, action)

    def is_terminal(self, state: State) -> bool:
        return self.mdp.is_terminal(state)

    def plan(self, state: State):
        return self.mdp.plan(state)

    # -- memoized cost signals -----------------------------------------
    def terminal_cost(self, state: State) -> float:
        tbl = self.cache.terminal
        c = tbl.get(state)
        if c is not None:
            self.cache.hits += 1
            return c
        self.cache.misses += 1
        c = tbl[state] = self.mdp.terminal_cost(state)
        return c

    def partial_cost(self, state: State) -> float:
        if self.mdp.is_terminal(state):
            return self.terminal_cost(state)
        tbl = self.cache.partial
        c = tbl.get(state)
        if c is not None:
            self.cache.hits += 1
            return c
        self.cache.misses += 1
        c = tbl[state] = self.mdp.partial_cost(state)
        return c

    # -- batched cost signals ------------------------------------------
    # Contract (shared by both methods): values equal the scalar methods
    # element-for-element; hits + misses advance by exactly len(states);
    # only MISSES reach the pricing layer, deduplicated, in first-occurrence
    # order — a state appearing twice in one batch is one miss plus one
    # hit, exactly as if the batch had been priced sequentially.  A warm
    # cache therefore never changes returned values, only the hit count.
    # The deduplicated miss batch reaches the wrapped MDP's batch methods:
    # one PlanColumns encode + one vectorized roofline-kernel pass per
    # miss batch.

    def _batch(self, states, tbl, price) -> List[float]:
        out: List[Optional[float]] = [None] * len(states)
        pending: Dict[State, None] = {}  # dedup, insertion-ordered
        hits = 0
        for i, s in enumerate(states):
            c = tbl.get(s)
            if c is not None:
                out[i] = c
                hits += 1
            elif s in pending:
                hits += 1  # duplicate miss: sequential order would hit
                self.cache.dedup += 1
            else:
                pending[s] = None
        self.cache.hits += hits
        self.cache.misses += len(pending)
        if pending:
            miss_states = list(pending)
            for s, c in zip(miss_states, price(miss_states)):
                tbl[s] = c
            for i, s in enumerate(states):
                if out[i] is None:
                    out[i] = tbl[s]
        return out

    def _price(self, batch_name: str, scalar):
        inner = getattr(self.mdp, batch_name, None)
        if inner is None:
            return lambda miss: [scalar(s) for s in miss]
        return inner

    def terminal_cost_batch(self, states: Sequence[State]) -> List[float]:
        return self._batch(
            states, self.cache.terminal,
            self._price("terminal_cost_batch", self.mdp.terminal_cost),
        )

    def partial_cost_batch(self, states: Sequence[State]) -> List[float]:
        """Mixed batches allowed: terminal states route to the terminal
        table (as the scalar ``partial_cost`` does)."""
        is_terminal = self.mdp.is_terminal
        term_idx = [i for i, s in enumerate(states) if is_terminal(s)]
        if not term_idx:
            return self._batch(
                states, self.cache.partial,
                self._price("partial_cost_batch", self.mdp.partial_cost),
            )
        term_set = set(term_idx)
        part_idx = [i for i in range(len(states)) if i not in term_set]
        out: List[Optional[float]] = [None] * len(states)
        for i, c in zip(term_idx,
                        self.terminal_cost_batch([states[i] for i in term_idx])):
            out[i] = c
        for i, c in zip(part_idx,
                        self.partial_cost_batch([states[i] for i in part_idx])):
            out[i] = c
        return out

    def __getattr__(self, name):
        # fall through for any extension attribute on the wrapped MDP;
        # dunders (and ``mdp`` itself, pre-__init__ during unpickling) must
        # raise, not recurse
        if name.startswith("_") or name == "mdp":
            raise AttributeError(name)
        return getattr(self.mdp, name)
