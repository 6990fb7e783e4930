"""The control of ``correct`` at tiny widths: the reference computed from
float8 inputs, put in the program's place, reads worse than the bf16
program does.  On the chip, at the cells' own sizes, the same readings set
each limit (``calibrate.py``; the readings are in PERF.md)."""
import pytest

from chipbench import calibrate, harness
from chipbench.tests import tiny

DEV = {"platform": "cpu", "kind": "cpu", "count": 1}


def _bf16_run(kind):
    run = tiny.make_run(kind)
    run.config["model"]["dtype"] = "bfloat16"
    run.config["program"]["overrides"]["dtype"] = "bfloat16"
    harness.execute(run, DEV)
    return run


@pytest.mark.parametrize("kind", ["train", "prefill"])
def test_control_reads_three_times_the_program(kind):
    run = _bf16_run(kind)
    got = calibrate.control(run)["control"]
    assert set(got) == set(run.checks)
    assert any(got[k] >= 3 * run.checks[k] for k in got), (got, run.checks)


def test_half_batch_fault_reads_above_the_program():
    run = _bf16_run("train")
    half = calibrate.control(run)["half_batch"]
    assert all(half[k] > run.checks[k] for k in half), (half, run.checks)


def test_serve_control_runs():
    """At tiny widths greedy decoding repeats tokens by wide margins, so
    neither the program nor the control moves a served token; the control
    still reads a number (the chip separates the two, PERF.md)."""
    run = _bf16_run("serve")
    got = calibrate.control(run)["control"]["served_logit_gap"]
    assert got is not None and got >= 0.0
