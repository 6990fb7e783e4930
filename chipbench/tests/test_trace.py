"""The trace reduction, on hand-made events and on two small traces
recorded once on a v5e chip (``record_trace.py``)."""
import gzip
import json
import pathlib

import numpy as np
import pytest

from chipbench import harness, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
V5E = {"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9, "chips": 1}


def _load(name):
    with gzip.open(DATA / f"{name}.json.gz", "rt") as f:
        return json.load(f)


def _op(name, shape="f32[8]"):
    return f"%{name}.1 = {shape} custom-call()"


def test_leaves_drop_wrappers_and_empty_events():
    ev = [[_op("while"), 0, 100], [_op("a"), 10, 20], [_op("b"), 40, 30],
          [_op("bitcast"), 40, 0]]
    assert [trace.op_name(n) for n, _, _ in trace.leaves(ev)] == ["a", "b"]


def test_interval_arithmetic():
    assert trace.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert trace.clip([(0, 3), (5, 7)], 2, 6) == [(2, 3), (5, 6)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert trace.length([(0, 2), (3, 5)]) == 4


def test_op_names_and_shapes():
    text = ("%moe_gemm.58 = bf16[32,1280,512]{2,1,0:T(8,128)(2,1)} custom-call("
            "bf16[32,1280,1024]{2,1,0} %x, bf16[32,1024,512]{2,1,0} %w), "
            "custom_call_target=\"tpu_custom_call\"")
    assert trace.op_name(text) == "moe_gemm"
    assert trace.arrays(text) == [("bf16", (32, 1280, 512)), ("bf16", (32, 1280, 1024)),
                                  ("bf16", (32, 1024, 512))]
    assert trace.nbytes("bf16", (32, 1280, 512)) == 2 * 32 * 1280 * 512


def test_reduce_by_hand():
    """Two devices, a 100 ns window: device 0 computes 0-40 and 60-80 and
    all-reduces 30-50, of which 40-50 has no compute beside it; device 1
    is busy 0-50.  The gaps of device 0 (50-60, 80-100) fall in spans 'a'
    and 'b'."""
    tr = {"devices": {
        "/device:TPU:0": [[_op("fusion"), 0, 40], [_op("all-reduce"), 30, 20],
                          [_op("fusion"), 60, 20]],
        "/device:TPU:1": [[_op("fusion"), 0, 50]]},
        "spans": [["chipbench.window", 0, 100], ["chipbench.a", 35, 30],
                  ["chipbench.b", 75, 30]]}
    r = trace.reduce(tr, 2)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx((70e-9 + 50e-9) / 2)
    assert r["collective_exposed_s"] == pytest.approx(10e-9 / 2)
    assert r["idle_by_span"] == pytest.approx({"a": 10e-9, "b": 20e-9})
    assert r["breakdown"]["idle_gaps"][0] == ["b", pytest.approx(20e-9)]


@pytest.mark.parametrize("name", ["prefill_2l", "train_2l"])
def test_recorded_busy_time_matches_a_timeline(name):
    """Busy time equals the count of 1 ns ticks covered by some leaf op."""
    tr = _load(name)
    r = trace.reduce(tr, 1)
    _, lo, dur = next(s for s in tr["spans"] if s[0] == "chipbench.window")
    tick = np.zeros(int(dur) + 1, bool)
    for n, s, d in trace.leaves(tr["devices"]["/device:TPU:0"]):
        a, b = max(s, lo) - lo, min(s + d, lo + dur) - lo
        if b > a:
            tick[int(round(a)):int(round(b))] = True
    assert r["busy_s"] == pytest.approx(tick.sum() * 1e-9, rel=1e-3)
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["window_s"] - r["busy_s"],
                                                            rel=1e-6)
    assert 0 < r["busy_s"] < r["window_s"]


def test_recorded_kernels_and_their_rooflines():
    pre, tra = trace.reduce(_load("prefill_2l"), 1), trace.reduce(_load("train_2l"), 1)
    calls = sum(1 for s in _load("prefill_2l")["spans"] if s[0] == "chipbench.prefill_call")
    # one flash-attention call per layer per prefill call (2 layers);
    # training runs each MoE layer's 3 expert GEMMs forward and again under
    # remat, 2 layers, 4 steps
    assert len(pre["kernels"]["flash_attention"]) in (2 * calls, 2 * calls - 2)
    assert len(tra["kernels"]["moe_gemm"]) == 3 * 2 * 2 * 4
    for name, red in (("flash_attention_roofline", pre), ("moe_gemm_roofline", tra)):
        share = harness.load_module(harness.HERE / "metrics" / f"{name}.py").read(
            {"trace": red, "peaks": V5E})
        assert 0 < share < 100
    none = harness.load_module(harness.HERE / "metrics" / "moe_gemm_roofline.py").read(
        {"trace": pre, "peaks": V5E})
    assert none is None  # no moe_gemm ran: nothing to read, never 0
