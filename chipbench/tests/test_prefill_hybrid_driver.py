"""The hybrid prefill cell's driver (``drivers/prefill_hybrid.py``) end to end
on the CPU at tiny widths, without the harness's look for a chip: the
program agrees with the reference, a fault planted under it comes out not
correct, and a configuration the program does not match is refused."""
import copy
import json
import time
import types

import pytest

from chipbench import harness
from chipbench.drivers import prefill_hybrid
from chipbench.tests.test_hybrid import HYBRID, OVERRIDES
from chipbench.tests.tiny import ROOT

DEV = {"platform": "cpu", "kind": "cpu", "count": 1}


def _run(seconds=0.5):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == "hybrid_prefill_8k")
    traffic = harness.read_json(harness.HERE / "traffic" / f"{cell['traffic']}.json")
    traffic.update(batch=1, seq_len=64)
    config = {"model": copy.deepcopy(HYBRID),
              "program": {"arch": "jamba2-3b", "overrides": dict(OVERRIDES)}}
    return harness.Run(bench, cell, config, traffic, {"logit_rel_err": 1e-4}, 12345678901,
                       seconds, False, time.perf_counter())


@pytest.fixture(scope="module")
def sound():
    """A sound run of the driver and its result line."""
    run = _run()
    return run, harness.execute(run, DEV)


def test_driver_runs_and_is_correct(sound):
    run, res = sound
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"prefill_tokens_s", "setup_s"}
    assert res["attempted"] > 0 and run.counts["layout_checked"]
    assert run.counts["plan"]["scan_chunk"] in (64, 128, 256)
    assert run.counts["window_compiles"] == 0


def test_driver_catches_norms_left_out(monkeypatch):
    """The program's Mamba mixers skip their dt/B/C norms under the driver:
    the run is not correct."""
    from repro.models import mamba

    monkeypatch.setattr(mamba, "ref", types.SimpleNamespace(rmsnorm=lambda x, w: x))
    res = harness.execute(_run(), DEV)
    assert not res["correct"], res["checks"]


def test_driver_refuses_a_config_the_program_does_not_match():
    run = _run()
    run.config["model"]["attn_layer_offset"] = 6
    with pytest.raises(ValueError):
        prefill_hybrid.program_config(run)


def test_calibration_reads_the_control_and_faults_above_the_program(sound):
    """``calibrate_hybrid.variants`` after a run: the float8 control and the
    two faults in the program's place each read far above the float32
    program (on the chip, at the cell's size, they set the limit)."""
    from chipbench import calibrate_hybrid

    run, _ = sound
    got = calibrate_hybrid.variants(run)
    assert set(got) == {"control", "attn_first", "no_ssm_norms"}
    for name, numbers in got.items():
        assert numbers["logit_rel_err"] > 100 * run.checks["logit_rel_err"], (name, numbers)
