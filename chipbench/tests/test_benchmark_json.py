"""BENCHMARK.json keeps to the benchmark's contract, and every name in it
resolves to a file of the harness: nothing in the harness names a cell."""
import json
import re

import pytest

from chipbench import harness
from chipbench.tests.tiny import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_names_units_and_lines():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for text in [c["why"] for c in BENCH["configs"] + BENCH["workloads"]] + [
            m["layer"] for m in BENCH["per_layer"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_configs_resolve():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/configs/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for k in c["reduced"]:  # a cut of depth, never of a width
            assert not k.endswith(("_dim", "_rank", "_size")) and "head" not in k
        for k, v in cfg["published"].items():
            if k in cfg["model"] and k not in c["reduced"]:
                assert cfg["model"][k] == v, k


def test_workloads_resolve():
    used = set()
    fours = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert fours <= max(1, len(BENCH["workloads"]) // 2)
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        traffic = json.loads((harness.HERE / "traffic" / f"{w['traffic']}.json").read_text())
        assert (harness.HERE / "drivers" / f"{traffic['driver']}.py").exists()
        assert (harness.HERE / "limits" / f"{w['name']}.json").exists()
    assert used == {c["name"] for c in BENCH["configs"]}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_reports_enough(cell):
    def mine(m):
        return cell in m.get("workloads", [cell])

    e2e = [m["name"] for m in BENCH["end_to_end"] if mine(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in BENCH["per_layer"] if cell in m.get("workloads", [])]
    assert layer
    for m in layer:  # each moves an end-to-end metric the cell reports
        assert m["moves"] in e2e


def test_metrics_resolve():
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25 for m in BENCH["end_to_end"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert (harness.HERE / "metrics" / f"{m['name']}.py").exists()
        assert hasattr(harness.load_module(harness.HERE / "metrics" / f"{m['name']}.py"), "read")
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        layers.setdefault(m["layer"], m["layer"])
    assert set(layers) == {"search and pricing", "step loop", "model step", "kernels", "device"}
