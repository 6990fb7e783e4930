"""The drivers end to end on the CPU at tiny widths, without the harness's
look for a chip: the program agrees with the reference, and a timed path
broken underneath comes out not correct."""
import pytest

from chipbench import harness
from chipbench.tests import tiny

DEV = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.mark.parametrize("kind", ["train", "prefill", "serve"])
def test_sound_run_is_correct(kind):
    run = tiny.make_run(kind)
    res = harness.execute(run, DEV)
    assert res["correct"], res["checks"]
    e2e = {"train": "train_tokens_s", "prefill": "prefill_tokens_s",
           "serve": "serve_tokens_s"}[kind]
    assert set(res["metrics"]) == {e2e, "setup_s"}
    assert res["metrics"][e2e]["value"] > 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"


def _unchanged_state(monkeypatch):
    from repro.training import trainer

    make = trainer.make_train_step

    def broken(*a, **k):
        step = make(*a, **k)

        def same(params, opt_state, batch):
            _, _, m = step(params, opt_state, batch)
            return params, opt_state, m

        return same

    monkeypatch.setattr(trainer, "make_train_step", broken)


def _half_batch(monkeypatch):
    from repro.training import train_step

    ce = train_step.cross_entropy

    def half(logits, labels):  # rows if there are two or more, else positions
        if logits.shape[0] > 1:
            return ce(logits[: logits.shape[0] // 2], labels[: labels.shape[0] // 2])
        return ce(logits[:, : logits.shape[1] // 2], labels[:, : labels.shape[1] // 2])

    monkeypatch.setattr(train_step, "cross_entropy", half)


def _answer_altered(monkeypatch):
    from repro.training import train_step

    make = train_step.make_prefill_step

    def broken(*a, **k):
        step = make(*a, **k)
        return lambda p, b: step(p, b).at[0, 3].set(step(p, b)[0, 4])

    monkeypatch.setattr(train_step, "make_prefill_step", broken)


def _token_altered(monkeypatch):
    from repro.serving import engine

    init = engine.ServingEngine.__init__

    def broken(self, cfg, *a, **k):
        init(self, cfg, *a, **k)
        decode = self._decode

        def altered(*args):
            tok, cache = decode(*args)
            return (tok + 1) % cfg.vocab_size, cache

        self._decode = altered

    monkeypatch.setattr(engine.ServingEngine, "__init__", broken)


@pytest.mark.parametrize("kind,fault", [
    ("train", _unchanged_state), ("train", _half_batch),
    ("prefill", _answer_altered), ("serve", _token_altered)])
def test_broken_path_is_not_correct(kind, fault, monkeypatch):
    fault(monkeypatch)
    res = harness.execute(tiny.make_run(kind), DEV)
    assert not res["correct"], res["checks"]


def test_unchanged_state_reads_one():
    """A step that returns its state unchanged reads 1 on the training
    cell's gradient and change numbers, and 0.5 on the direction (a zero
    first gradient): nothing moved."""
    import numpy as np

    from chipbench.drivers import train
    from chipbench.reference import granite as ref

    want = {"grad1": {"a": 2.0, "b": 3.0}, "grad1_raw": {"a": 2.0, "b": 3.0},
            "change": {"a": 0.5, "b": 0.7}}
    ref_grad = {"a": np.full(4, 1.0, np.float32), "b": np.arange(3, dtype=np.float32)}
    dirs = ref.dir_gaps({"a": np.zeros(4, np.float32), "b": np.zeros(3, np.float32)}, ref_grad)
    got = train.compare({"a": 0.0, "b": 0.0}, {"a": 0.0, "b": 0.0}, dirs, want)
    assert got["grad_gap"] == 1.0 and got["change_gap"] == 1.0
    assert abs(got["grad_dir_gap"] - 0.5) < 1e-6
