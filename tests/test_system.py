"""End-to-end behaviour tests: autotune → train → checkpoint → failure →
elastic resume → serve, on reduced configs."""
import numpy as np
import pytest

# full end-to-end flows (autotune -> train -> serve, CLI subprocesses,
# learned-cost training) — the long tail of the suite
pytestmark = pytest.mark.slow

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.core.autotuner import autotune
from repro.core.space import SchedulePlan


def test_autotune_then_train_then_serve(tmp_path):
    import jax

    from repro.serving.engine import ServingEngine
    from repro.training.trainer import Trainer, TrainerConfig

    # 1. autotune the REAL cell (full config, analytic model) — the plan's
    #    kernel/remat knobs transfer to the smoke run
    res = autotune("granite-3-2b", "train_4k", algo="mcts_1s", seed=0,
                   n_standard=2, n_greedy=1)
    assert res.plan is not None

    # 2. train a reduced model with (a safe projection of) that plan
    cfg = get_config("granite-3-2b").reduced()
    shape = InputShape("t", 32, 4, "train")
    plan = SchedulePlan(microbatches=2, remat=res.plan.remat,
                        opt_dtype=res.plan.opt_dtype)
    tc = TrainerConfig(total_steps=8, ckpt_every=4, ckpt_dir=str(tmp_path),
                       log_every=2, ckpt_async=False)
    trainer = Trainer(cfg, shape, plan, tc)
    params, _, step = trainer.run()
    assert step == 8

    # 3. simulated node failure -> elastic restart plan from checkpoint
    plan2 = trainer.handle_failure(["h0", "h1", "h2"], chips_per_host=4,
                                   model_parallel=4)
    assert plan2.restart_step == 8
    assert plan2.data_parallel >= 1

    # 4. serve with the trained weights
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=32)
    eng.submit(np.array([1, 2, 3]), max_new_tokens=4)
    eng.submit(np.array([9]), max_new_tokens=4)
    done = eng.run()
    assert len(done) == 2
    assert all(len(r.generated) == 4 for r in done)


def test_learned_cost_model_trains_and_ranks():
    from repro.core.autotuner import make_mdp
    from repro.core.learned_cost import ranking_correlation, train_learned_cost

    mdp = make_mdp("phi3.5-moe-42b-a6.6b", "train_4k")
    lcm = train_learned_cost(mdp.space, mdp.cost_model, n_samples=192, steps=250)
    rc = ranking_correlation(lcm, mdp.cost_model, mdp.space, n=96)
    assert rc > 0.5, rc


def test_cli_entrypoints_smoke(capsys, tmp_path, no_compile_cache):
    from repro.launch.serve import main as serve_main
    from repro.launch.train import main as train_main

    assert train_main(["--arch", "granite-3-2b", "--smoke", "--steps", "4",
                       "--ckpt-dir", str(tmp_path / "ckpt")]) == 0
    assert serve_main(["--arch", "granite-3-2b", "--smoke",
                       "--requests", "2", "--max-new", "3"]) == 0
    out = capsys.readouterr().out
    assert "[train] done" in out and "completed 2/2" in out
