"""Shared fixtures. NOTE: never set xla_force_host_platform_device_count
here — smoke tests and benches must see 1 device; multi-device tests run in
subprocesses (see test_distributed.py)."""
import os
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


@pytest.fixture(scope="session")
def rng_key():
    import jax

    return jax.random.PRNGKey(0)


@pytest.fixture
def no_compile_cache(monkeypatch):
    """For tests that call the launchers: their compile-cache helper would
    turn JAX's persistent cache on for the rest of the worker process."""
    from repro.launch import compile_cache

    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: None)


# ---------------------------------------------------------------------------
# Table-1 cell construction — ONE definition shared by the differential /
# engine / serving / service suites (each used to carry its own copy).
# Import as ``from conftest import TABLE1_CELLS, make_cell_mdp``.
# ---------------------------------------------------------------------------
MOE_TRAIN_CELL = ("granite-moe-1b-a400m", "train_4k")  # the MoE train cell
DECODE_CELL = ("granite-3-2b", "decode_32k")           # the decode cell
TRAIN_CELL = ("granite-3-2b", "train_4k")              # the dense train cell

# the differential grid's two headline cells (paper Table 1)
TABLE1_CELLS = {"moe_train": MOE_TRAIN_CELL, "decode": DECODE_CELL}


def make_cell_mdp(arch, shape_name, *, reduced=True, pricing=None,
                  columnar_min_batch=None):
    """A fresh ``ScheduleMDP`` for one Table-1 cell.

    ``reduced=True`` (the suites' default) shrinks the arch config so
    search grids stay inside the tier-1 budget; ``pricing`` /
    ``columnar_min_batch`` pass straight through to ``AnalyticCostModel``
    (None → the production defaults).  Engine-parity tests that need the
    FULL config use ``repro.core.autotuner.make_mdp`` directly."""
    from repro.configs import get_config, get_shape
    from repro.core.cost_model import AnalyticCostModel
    from repro.core.mdp import ScheduleMDP
    from repro.core.space import SINGLE_POD, ScheduleSpace

    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    shape = get_shape(shape_name)
    space = ScheduleSpace(cfg, shape, SINGLE_POD)
    cm = AnalyticCostModel(cfg, shape, SINGLE_POD, pricing=pricing,
                          columnar_min_batch=columnar_min_batch)
    return ScheduleMDP(space, cm)
