"""The planted faults of the hybrid prefill cell against its committed limit.

``calibrate_hybrid.py`` reads, beside the program and the float8 control
(which ``test_readings.py`` holds), the reference with each period's
attention layer run first (``attn_first``) and with the Mamba mixers'
dt/B/C norms left out (``no_ssm_norms``).  Each fault fails the limit on
every seed it was read on, three seeds or more."""
import json
import types

import pytest

from chipbench import harness
from chipbench.calibrate_hybrid import VARIANTS

CELL = "hybrid_prefill_8k"
READINGS = harness.HERE / "tests" / "data" / f"{CELL}.readings.jsonl"


@pytest.mark.parametrize("fault", [v for v in VARIANTS if v != "control"])
def test_fault_fails_the_limit_on_every_seed(fault):
    limits = harness.read_json(harness.HERE / "limits" / f"{CELL}.json")
    lines = [json.loads(x) for x in READINGS.read_text().splitlines() if x.strip()]
    read = [r for r in lines if fault in r]
    assert len({r["seed"] for r in read}) >= 3
    for r in read:
        assert set(r[fault]) == set(limits)
        run = types.SimpleNamespace(checks=r[fault], limits=limits)
        assert not harness.is_correct(run), (r["seed"], r[fault], limits)
