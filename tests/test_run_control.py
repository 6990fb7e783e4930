"""Round-boundary run control: deadlines, cancellation, checkpoints.

Certifies the ``RunController`` seam (``core/run_control.py``) at both
layers:

* unit level, on a fake clock — deadline arming, cancel-only round
  truncation, checkpoint cadence, and per-round checkpoint idempotence;
* engine level, on the dense train cell — an interrupted ensemble run
  checkpoints at a completed round boundary and a resumed run replays
  the exact tail, bit-identical (plan, cost, decisions) to the
  uninterrupted reference, for each engine and round mode, plus the
  evolutionary backend's
  generation-boundary interrupt (best-so-far prefix, no checkpoints).

The daemon-level legs (SIGKILL resume, journal replay) live in
``tests/test_tuner_service.py``.
"""
import pickle

import pytest
from conftest import TRAIN_CELL as CELL
from conftest import make_cell_mdp

from repro.core.autotuner import autotune
from repro.core.run_control import RunController


def _ref(seed=0):
    return autotune(CELL[0], CELL[1], algo="mcts_1s", seed=seed,
                    n_standard=2, n_greedy=1)


# ---------------------------------------------------------------------------
# unit: the controller itself, on a fake clock
# ---------------------------------------------------------------------------
class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_deadline_fires_on_injected_clock():
    clk = FakeClock()
    con = RunController(deadline_s=10.0, clock=clk)
    assert con.should_stop() is None
    clk.t += 9.999
    assert con.should_stop() is None
    clk.t += 0.002
    assert con.should_stop() == "deadline"
    # a deadline NEVER truncates a round — abort_round answers only to
    # cancel, so every deadline checkpoint lands on a canonical boundary
    assert con.abort_round() is False
    assert con.round_truncated is False


def test_cancel_truncates_and_wins_over_deadline():
    clk = FakeClock()
    con = RunController(deadline_s=10.0, clock=clk)
    assert con.abort_round() is False
    con.cancel()
    assert con.cancelled
    assert con.abort_round() is True
    assert con.round_truncated is True
    con.begin_round()  # per-round flag resets at the next boundary
    assert con.round_truncated is False
    clk.t += 100.0  # even past the deadline, cancel is the reported reason
    assert con.should_stop() == "cancelled"


def test_no_deadline_runs_forever():
    con = RunController(clock=FakeClock())
    assert con.deadline is None
    for _ in range(5):
        con.begin_round()
        con.round_done()
    assert con.should_stop() is None and con.n_rounds == 5


def test_checkpoint_cadence_and_per_round_idempotence():
    sink = []
    con = RunController(checkpoint_every=2, checkpoint_fn=sink.append)
    thunk = lambda: {"round": con.n_rounds}  # noqa: E731
    for _ in range(4):
        con.begin_round()
        con.round_done(thunk)
    # cadence: rounds 2 and 4 checkpointed, lazily built from the thunk
    assert [s["round"] for s in sink] == [2, 4]
    assert con.n_checkpoints == 2
    # a final interrupt checkpoint on a cadence round writes nothing new
    assert con.checkpoint(thunk) is True and len(sink) == 2
    # ...but on an off-cadence round it does
    con.begin_round()
    con.round_done(thunk)
    assert con.checkpoint(thunk) is True
    assert [s["round"] for s in sink] == [2, 4, 5]
    # with no sink (or no thunk) there is no checkpoint to report
    assert RunController().checkpoint(thunk) is False
    assert con.checkpoint(None) is False


# ---------------------------------------------------------------------------
# engine: interrupt + resume is bit-identical to the uninterrupted run
# ---------------------------------------------------------------------------
def _cancelling_sink(after: int):
    """A checkpoint sink that cancels its controller after ``after``
    checkpoints land — a deterministic interrupt at an exact round
    boundary (no wall-clock in the loop)."""
    snaps = []
    box = {}

    def fn(snap):
        snaps.append(pickle.dumps(snap))  # like the store: freeze at write
        if len(snaps) >= after:
            box["con"].cancel()

    return snaps, box, fn


# every engine × round mode a snapshot can carry; the reference engine
# has no lockstep rounds (``batch`` only batches array trees)
@pytest.mark.parametrize("engine,batch", [("array", True), ("array", False),
                                          ("reference", False)])
def test_interrupt_then_resume_bit_identical(engine, batch):
    ref = _ref()
    rounds_total = len(ref.decisions)
    assert rounds_total > 6

    snaps, box, fn = _cancelling_sink(after=5)
    con = RunController(checkpoint_every=1, checkpoint_fn=fn)
    box["con"] = con
    cut = autotune(CELL[0], CELL[1], algo="mcts_1s", seed=0,
                   n_standard=2, n_greedy=1, engine=engine, batch=batch,
                   controller=con)
    info = cut.stats["interrupted"]
    assert info["reason"] == "cancelled"
    assert info["rounds_done"] == 5 and info["rounds_total"] == rounds_total
    # cancel landed inside round_done's checkpoint → boundary was clean
    assert info["round_truncated"] is False and info["checkpointed"] is True
    assert cut.decisions == ref.decisions[:5]  # best-so-far is a true prefix

    # resume from the frozen checkpoint: the tail replays bit-identically
    snap = pickle.loads(snaps[-1])
    assert snap["engine"] == engine
    res = autotune(CELL[0], CELL[1], algo="mcts_1s", seed=0,
                   n_standard=2, n_greedy=1, engine=engine, batch=batch,
                   resume=snap)
    assert res.plan == ref.plan and res.cost == ref.cost
    assert res.decisions == ref.decisions
    assert "interrupted" not in (res.stats or {})


def test_uninterrupted_controller_is_inert():
    """A mounted controller that never fires must not perturb the search
    (it reads a clock and an event; it never touches search state)."""
    ref = _ref()
    sink = []
    con = RunController(deadline_s=3600.0, checkpoint_every=3,
                        checkpoint_fn=lambda s: sink.append(True))
    res = autotune(CELL[0], CELL[1], algo="mcts_1s", seed=0,
                   n_standard=2, n_greedy=1, controller=con)
    assert res.plan == ref.plan and res.cost == ref.cost
    assert res.decisions == ref.decisions
    assert "interrupted" not in (res.stats or {})
    assert con.n_rounds == len(ref.decisions) and sink


def test_mid_round_cancel_never_checkpoints_truncated_round():
    """A cancel that lands MID-round (engine/batch.py's iteration poll)
    truncates that round; the truncated round must not be counted,
    checkpointed, or reported as a clean boundary."""
    ref = _ref()
    snaps = []

    con = RunController(checkpoint_every=1,
                        checkpoint_fn=lambda s: snaps.append(len(s["decisions"])))
    con.cancel()  # cancelled before round 1 → the first round truncates
    cut = autotune(CELL[0], CELL[1], algo="mcts_1s", seed=0,
                   n_standard=2, n_greedy=1, controller=con)
    info = cut.stats["interrupted"]
    assert info["reason"] == "cancelled"
    assert info["round_truncated"] is True and info["checkpointed"] is False
    assert snaps == [] and con.n_rounds == 0
    # the engine still finishes the (shortened) round: one decision lands
    assert info["rounds_done"] == 1
    assert len(cut.decisions) == 1
    assert cut.decisions[0]["action"] == ref.decisions[0]["action"]


def test_evolve_backend_deadline_interrupt_is_prefix():
    """The evolutionary backend honors the controller at generation
    boundaries: best-so-far out, decisions a true prefix, and — since an
    evolve replay from scratch is cheap and deterministic — never a
    checkpoint."""
    from repro.core.engine import CachedMDP
    from repro.core.evolve import EvolutionarySearchBackend

    def backend():
        return EvolutionarySearchBackend(population=16, generations=8)

    ref = backend().run(CachedMDP(make_cell_mdp(*CELL)), seed=0)
    assert len(ref.decisions) == 8

    clk = FakeClock()
    con = RunController(deadline_s=1e-9, clock=clk,
                        checkpoint_every=1,
                        checkpoint_fn=lambda s: pytest.fail("no checkpoints"))
    clk.t += 1.0  # deadline already lapsed at the first boundary
    cut = backend().run(CachedMDP(make_cell_mdp(*CELL)), seed=0,
                        controller=con)
    info = cut.stats["interrupted"]
    assert info["reason"] == "deadline" and info["checkpointed"] is False
    assert 0 < info["rounds_done"] < info["rounds_total"] == 8
    assert cut.decisions == ref.decisions[:info["rounds_done"]]
    assert cut.cost == cut.decisions[-1]["best_cost"]
