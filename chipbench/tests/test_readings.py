"""The committed limits against the readings they were set from.

Each ``data/<cell>.readings.jsonl`` line is one seed of ``calibrate.py`` on
the chip at the cell's own size: the program's numbers and, on some seeds,
the control's (the float8 reference in the program's place) and a planted
fault's.  Every program reading passes ``limits/<cell>.json``; the control
and every fault fail it on every seed they were read on."""
import json
import types

import pytest

from chipbench import harness

DATA = harness.HERE / "tests" / "data"
FILES = sorted(DATA.glob("*.readings.jsonl"))


def _lines(path):
    return [json.loads(line) for line in path.read_text().splitlines() if line.strip()]


def _correct(numbers: dict, limits: dict) -> bool:
    return harness.is_correct(types.SimpleNamespace(checks=numbers, limits=limits))


def test_every_cell_has_readings():
    cells = {w["name"] for w in harness.read_json(harness.ROOT / "BENCHMARK.json")["workloads"]}
    assert {p.name.split(".")[0] for p in FILES} == cells


@pytest.mark.parametrize("path", FILES, ids=lambda p: p.name.split(".")[0])
def test_program_passes_and_control_and_faults_fail(path):
    limits = harness.read_json(harness.HERE / "limits" / f"{path.name.split('.')[0]}.json")
    lines = _lines(path)
    assert len({r["seed"] for r in lines}) >= 12
    read = 0
    for r in lines:
        assert _correct(r["program"], limits), (r["seed"], r["program"], limits)
        for side in ("control", "half_batch"):
            if side in r:
                read += 1
                assert not _correct(r[side], limits), (r["seed"], side, r[side], limits)
    assert read >= 3
