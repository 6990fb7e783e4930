"""Fault tolerance control-plane tests: heartbeats, rendezvous re-balance,
straggler eviction, elastic restart plans — plus the measurement fleet's
retry/quarantine/watchdog machinery
(``repro.core.measure_fleet``; all via the XLA-free stub target)."""
import itertools
import json
import os

import pytest

from repro.runtime.fault_tolerance import (
    ElasticPlan,
    HeartbeatMonitor,
    StragglerPolicy,
    plan_restart,
    rebalance,
)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_heartbeat_detects_dead_host():
    clock = FakeClock()
    mon = HeartbeatMonitor(["h0", "h1", "h2"], timeout=10, clock=clock)
    clock.t = 5
    mon.beat("h0")
    mon.beat("h1")
    clock.t = 12
    assert mon.dead_hosts() == ["h2"]
    assert mon.alive_hosts() == ["h0", "h1"]


def test_rebalance_minimal_movement():
    hosts = [f"h{i}" for i in range(8)]
    before = rebalance(hosts, 64)
    after = rebalance([h for h in hosts if h != "h3"], 64)
    moved = [s for s in range(64) if before[s] != after[s]]
    # only shards that lived on the dead host move (rendezvous property)
    assert set(moved) == {s for s, h in before.items() if h == "h3"}
    # and the survivors' assignment is complete
    assert set(after) == set(range(64))
    assert "h3" not in after.values()


def test_straggler_eviction_after_repeat_offenses():
    pol = StragglerPolicy(threshold=1.5, evict_after=3, ewma=0.0)
    for step in range(4):
        for h in ("h0", "h1", "h2", "h3"):
            pol.observe(h, 1.0 if h != "h2" else 3.0)
        flagged = pol.stragglers()
        assert flagged == ["h2"]
    assert pol.evictions() == ["h2"]


def test_elastic_plan_shrinks_data_axis():
    alive = [f"h{i}" for i in range(7)]  # lost 1 of 8 hosts, 4 chips each
    plan = plan_restart(alive, chips_per_host=4, model_parallel=4,
                        latest_ckpt_step=120, global_batch=256)
    assert plan.restart_step == 120
    # 28 chips / mp 4 -> dp 7, shrunk to 4 so it divides the global batch
    assert plan.data_parallel == 4
    assert 256 % plan.data_parallel == 0


def test_elastic_plan_divides_batch():
    alive = [f"h{i}" for i in range(6)]
    plan = plan_restart(alive, chips_per_host=4, model_parallel=4,
                        latest_ckpt_step=10, global_batch=16)
    assert 16 % plan.data_parallel == 0
    assert plan.data_parallel <= 6


def test_elastic_plan_shard_map_covers_all_shards():
    alive = ["a", "b", "c"]
    plan = plan_restart(alive, 4, 4, 0, 12)
    shards = dict(plan.shard_map)
    assert sorted(shards) == list(range(plan.data_parallel))
    assert set(shards.values()) <= set(alive)


def test_rebalanced_pipeline_is_exact():
    """After a host dies, survivors recompute the lost shards exactly
    (stateless index math)."""
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.data.pipeline import DataConfig, Pipeline

    cfg = get_config("granite-3-2b").reduced()
    shape = InputShape("t", 16, 8, "train")
    # original 4-host layout
    orig = [
        Pipeline(cfg, shape, DataConfig(host_count=4, host_index=h)).batch_at(5)
        for h in range(4)
    ]
    # any survivor can recompute host 2's shard for step 5
    recomputed = Pipeline(
        cfg, shape, DataConfig(host_count=4, host_index=0)
    ).batch_at(5, host_index=2)
    import numpy as np

    np.testing.assert_array_equal(recomputed["inputs"], orig[2]["inputs"])


# ---------------------------------------------------------------------------
# Measurement cache + fleet (core/measure, core/measure_fleet)
# ---------------------------------------------------------------------------
CELL = ("granite-3-2b", "train_4k")


def _fleet(tmp_path, n=2, **kw):
    from repro.core.measure_fleet import MeasurementFleet
    from repro.core.measure_stub import stub_measure

    kw.setdefault("cache_dir", str(tmp_path / "fleet_cache"))
    kw.setdefault("target", stub_measure)
    kw.setdefault("timeout", 30.0)
    kw.setdefault("grace_s", 10.0)
    kw.setdefault("backoff_s", 0.05)
    return MeasurementFleet(n, **kw)


def test_measure_cache_poisoning_quarantined(tmp_path):
    """A truncated JSON at the cache path (the pre-fix poisoning mode:
    a crashed compile writing straight to the final path) must be
    quarantined and re-measured — not served as a hit, not a crash."""
    from repro.core.measure import make_request, measure_cell, request_key
    from repro.core.measure_stub import stub_measure

    cache = str(tmp_path / "cache")
    rec = measure_cell(*CELL, cache_dir=cache, target=stub_measure)
    key = request_key(make_request(*CELL))
    path = os.path.join(cache, key + ".json")
    with open(path, "w") as f:
        f.write('{"step_s": 0.0')  # truncated: a torn pre-atomic write
    again = measure_cell(*CELL, cache_dir=cache, target=stub_measure)
    assert again == rec  # re-measured, corrupt entry gone
    # and the re-measured record now serves as a clean hit
    calls = {"n": 0}

    def counting(req):
        calls["n"] += 1
        return stub_measure(req)

    assert measure_cell(*CELL, cache_dir=cache, target=counting) == rec
    assert calls["n"] == 0


def test_cache_key_includes_devices():
    """Pre-fix, measuring the same cell at a different forced device
    count silently returned the first count's record."""
    from repro.core.measure import make_request, request_key

    base = request_key(make_request(*CELL))
    assert request_key(make_request(*CELL, devices=8)) != base
    assert request_key(make_request(*CELL, devices=16)) != request_key(
        make_request(*CELL, devices=8)
    )
    # extras are transport-only: they must never perturb the key
    assert request_key(make_request(*CELL, extras={"inject": {}})) == base


def test_timeout_surfaces_runtime_error_without_residue(tmp_path, monkeypatch):
    """``subprocess.TimeoutExpired`` must surface as the standard
    RuntimeError (naming the timeout) and leave nothing on disk."""
    from repro.core import measure

    monkeypatch.setattr(measure, "DRYRUN_MODULE", "repro.launch.dryrun_stub")
    monkeypatch.setenv("REPRO_STUB_SLEEP_S", "30")
    cache = str(tmp_path / "cache")
    with pytest.raises(RuntimeError, match="timed out after 1s"):
        measure.measure_cell(*CELL, cache_dir=cache, timeout=1.0)
    assert os.listdir(cache) == []  # no partial record, no tmp residue


def test_fleet_worker_sigkill_retries_identical_to_serial(tmp_path):
    """SIGKILL a fleet worker mid-request: the master respawns it,
    re-dispatches the request within the retry budget, and the cache
    record is byte-identical to the serial measure_cell path."""
    from repro.core.measure import make_request, measure_cell, request_key
    from repro.core.measure_stub import stub_measure

    serial_cache = str(tmp_path / "serial_cache")
    with _fleet(tmp_path) as fleet:
        marker = str(tmp_path / "kill.marker")
        req = make_request(
            *CELL, extras={"inject": {"marker": marker, "kind": "kill"}}
        )
        out = fleet.measure_many([req])[0]
        assert out.ok
        assert out.worker_deaths == 1 and out.retries == 1
        assert fleet.n_worker_restarts == 1
        serial = measure_cell(
            *CELL, cache_dir=serial_cache, target=stub_measure
        )
        assert out.record == serial
        key = request_key(req)
        with open(os.path.join(fleet.cache_dir, key + ".json"), "rb") as f:
            fleet_bytes = f.read()
        with open(os.path.join(serial_cache, key + ".json"), "rb") as f:
            assert f.read() == fleet_bytes


def test_fleet_quarantines_corrupt_cache_entry(tmp_path):
    from repro.core.measure import make_request, request_key

    with _fleet(tmp_path) as fleet:
        req = make_request(*CELL)
        os.makedirs(fleet.cache_dir, exist_ok=True)
        path = os.path.join(fleet.cache_dir, request_key(req) + ".json")
        with open(path, "w") as f:
            f.write("not json at all")
        out = fleet.measure_many([req])[0]
        assert out.ok and not out.from_cache
        assert fleet.n_measured == 1 and fleet.n_cache_hits == 0
        with open(path) as f:
            assert json.load(f)["step_s"] == out.record["step_s"]


def test_fleet_single_flight_dedup(tmp_path):
    """Five concurrent requests for the same plan compile once; all five
    share the record.  A second batch is pure cache hits."""
    from repro.core.measure import make_request

    with _fleet(tmp_path) as fleet:
        outs = fleet.measure_many([make_request(*CELL) for _ in range(5)])
        assert all(o.ok for o in outs)
        assert fleet.n_measured == 1 and fleet.n_deduped == 4
        assert len({id(o) for o in outs}) == 1  # one shared outcome
        again = fleet.measure_many([make_request(*CELL)])
        assert again[0].from_cache and fleet.n_measured == 1


def test_fleet_watchdog_kills_stalled_worker(tmp_path):
    """A worker stalled past (timeout + grace) is killed and the request
    re-dispatched; the injection fires once so the retry succeeds."""
    from repro.core.measure import make_request

    with _fleet(tmp_path, n=1, timeout=0.4, grace_s=0.4) as fleet:
        marker = str(tmp_path / "sleep.marker")
        req = make_request(
            *CELL, timeout=0.4,
            extras={"inject": {"marker": marker, "kind": "sleep",
                               "sleep_s": 30}},
        )
        out = fleet.measure_many([req])[0]
        assert out.ok
        assert out.timeouts == 1 and out.retries == 1
        assert fleet.n_timeouts == 1 and fleet.n_worker_restarts == 1


def test_sweep_resume_retries_failed_measurements(tmp_path):
    """A stored sweep row whose measurement FAILED must not mark its key
    done: pre-fix, ``stored_keys`` counted every stored row, so a
    transient fleet failure (``measured_step_s: null``) was never
    re-measured on resume."""
    from benchmarks.sweep import run_sweep, stored_keys
    from repro.core.measure_stub import failing_measure

    spec = {
        "name": "retry",
        "defaults": {"algo": "mcts_1s", "n_standard": 2, "n_greedy": 1},
        "matrix": {"cell": [list(CELL)]},
    }
    common = dict(results_dir=str(tmp_path), measure="stub", workers=1,
                  log=lambda *a: None)
    cache_dir = str(tmp_path / "mc")
    rows1 = run_sweep(spec, fleet_kwargs={
        "target": failing_measure, "max_retries": 0, "cache_dir": cache_dir,
    }, **common)
    assert rows1[0]["measured_step_s"] is None
    assert rows1[0]["measure"]["failed"]
    out_path = os.path.join(str(tmp_path), "retry.jsonl")
    assert stored_keys(out_path) == set()  # a failed row is NOT done
    # resume with a healthy fleet: the row re-runs and sticks
    rows2 = run_sweep(spec, fleet_kwargs={"cache_dir": cache_dir}, **common)
    assert len(rows2) == 1, "resume skipped the failed row"
    assert rows2[0]["measured_step_s"] is not None
    assert stored_keys(out_path) == {rows2[0]["key"]}
    # and a THIRD resume now runs nothing
    assert run_sweep(spec, fleet_kwargs={"cache_dir": cache_dir},
                     **common) == []


def test_fleet_exhausted_retries_fail_without_raising(tmp_path):
    from repro.core.measure import make_request
    from repro.core.measure_stub import failing_measure

    with _fleet(tmp_path, n=1, target=failing_measure, max_retries=1) as fleet:
        out = fleet.measure_many([make_request(*CELL)])[0]
        assert not out.ok and out.retries == 1
        assert "deliberate failure" in out.error
        assert fleet.n_failures == 1
        assert os.listdir(fleet.cache_dir) == []  # failures never cached
        with pytest.raises(RuntimeError, match="deliberate failure"):
            fleet.measure_cell(*CELL)


def test_measure_failure_degrades_to_analytic():
    """A raising measure_fn inside mcts_cost+real_* must not kill the
    run: the candidate re-ranks by its exact analytic cost and the
    failure is counted on TuneResult.n_measure_failures."""
    from repro.core.autotuner import make_mdp
    from repro.core.ensemble import ProTuner
    from repro.core.mcts import MCTSConfig

    calls = {"n": 0}

    def flaky(plan):
        calls["n"] += 1
        raise RuntimeError("compile exploded")

    mdp = make_mdp(*CELL)
    tuner = ProTuner(
        mdp, n_standard=2, n_greedy=1,
        mcts_config=MCTSConfig(iters_per_decision=4), seed=3,
        measure_fn=flaky,
    )
    res = tuner.run()
    assert calls["n"] > 0
    assert res.n_measure_failures > 0
    assert res.measured is None  # degraded analytic values are not
    assert res.cost > 0          # reported as real measurements
    # and the run matches a plain un-measured run's final schedule
    plain = ProTuner(
        make_mdp(*CELL), n_standard=2, n_greedy=1,
        mcts_config=MCTSConfig(iters_per_decision=4), seed=3,
    ).run()
    assert res.plan == plain.plan


def test_fleet_backend_batches_ensemble_measurements(tmp_path):
    """measure_backend= threads a fleet through the ensemble: candidate
    measurements prefetch through measure_plans, results match the
    serial measure_fn path, and failures degrade per-candidate."""
    from repro.core.autotuner import make_mdp
    from repro.core.ensemble import ProTuner
    from repro.core.mcts import MCTSConfig
    from repro.core.measure_stub import stub_measure

    def serial_fn(plan):
        return stub_measure(
            {"arch": CELL[0], "shape": CELL[1], "mesh": "single",
             "plan": plan.to_dict(), "devices": None}
        )["step_s"]

    cfg = MCTSConfig(iters_per_decision=4)
    serial = ProTuner(
        make_mdp(*CELL), n_standard=2, n_greedy=1, mcts_config=cfg,
        seed=5, measure_fn=serial_fn,
    ).run()
    with _fleet(tmp_path) as fleet:
        backend = fleet.bind(*CELL)
        res = ProTuner(
            make_mdp(*CELL), n_standard=2, n_greedy=1, mcts_config=cfg,
            seed=5, measure_backend=backend,
        ).run()
        assert fleet.n_measured > 0  # prefetches actually hit the fleet
    assert res.plan == serial.plan
    assert res.measured == pytest.approx(serial.measured)
    assert res.n_measure_failures == 0
    assert res.n_measurements == serial.n_measurements
