"""chip_smoke.py's phases at ``cfg.reduced()`` size, on the CPU, with the
Pallas kernels in interpret mode; and its refusal to run without a TPU."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402


def _granite():
    from repro.configs import get_config

    return get_config("granite-3-2b").reduced()


def _moe():
    from repro.configs import get_config

    return get_config("granite-moe-1b-a400m").reduced()


def test_phase_tune_reports_applied_fields(capsys):
    rec, plans = cs.phase_tune()
    assert rec["ok"] and set(plans) == {"prefill", "serve", "train"}
    for use in ("prefill", "serve", "train"):
        assert set(rec["applied"][use]) >= {"tiles", "remat", "microbatches",
                                            "opt_dtype"}
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "tune" and line["device"]["platform"] == "cpu"


def test_phase_kernels_interpret():
    from repro.kernels.ops import KernelTiles, kernel_mode

    tiles = KernelTiles(attn_block_q=64, attn_block_kv=64, scan_chunk=32,
                        scan_d_block=128, moe_block_c=16, moe_block_f=32,
                        moe_block_d=64)
    with kernel_mode("interpret"):
        rec = cs.phase_kernels(tiles, {
            "attention": dict(B=1, H=4, Hkv=2, S=128, D=64),
            "rmsnorm": dict(rows=64, d=128),
            "moe_gemm": dict(E=4, C=32, d=128, f=64),
            "selective_scan": dict(L=64, Di=256, N=16)})
    assert rec["ok"], rec["checks"]
    assert set(rec["errors"]) == {"attention", "rmsnorm", "moe_gemm",
                                  "selective_scan"}


def test_phase_prefill_and_serve_interpret():
    from repro.core.space import SchedulePlan
    from repro.kernels.ops import kernel_mode

    cfg = _granite()
    with kernel_mode("interpret"):
        rec, params = cs.phase_prefill(cfg, SchedulePlan(attn_block=(32, 32)),
                                       batch=1, seq=64)
        assert rec["ok"], rec["checks"]
        assert 0 < rec["logits_rel_l2_vs_ref"] <= 1e-5  # kernels ran, f32 agrees
        srv = cs.phase_serve(cfg, params, SchedulePlan(), slots=4, max_len=64,
                             n_requests=5, prompt_len=(2, 8), new_tokens=4)
    assert srv["ok"], srv["checks"]


def test_phase_train_custom_vjp_grads_match_ref():
    from repro.core.space import SchedulePlan
    from repro.kernels.ops import kernel_mode

    tuned = SchedulePlan(remat="dots", microbatches=4, attn_block=(32, 32),
                         opt_dtype="int8")
    plan = cs._train_plan(tuned, batch=2)
    assert plan.microbatches == 2 and plan.opt_dtype == "float32"
    with kernel_mode("interpret"):
        rec = cs.phase_train(_moe(), plan, batch=2, seq=64, steps=3, cut="reduced")
    assert rec["ok"], rec["checks"]
    assert 0 < rec["grads_rel_l2_vs_ref"] <= 1e-4  # custom_vjp path, f32
    assert len(rec["losses"]) == 3


def test_phase_mesh_on_four_host_devices():
    code = (
        "import chip_smoke as cs\n"
        "from repro.configs import get_config\n"
        "from repro.kernels.ops import kernel_mode\n"
        "with kernel_mode('interpret'):\n"
        "    rec = cs.phase_mesh(get_config('granite-moe-1b-a400m').reduced(),\n"
        "                        cut_layers=1, batch=2, seq=64, steps=2)\n"
        "raise SystemExit(0 if rec['ok'] else 1)\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600, cwd=ROOT, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["device"]["count"] == 4 and rec["checks"]["state_spread"]
    assert rec["compare"]["loss_mesh"] == pytest.approx(
        rec["compare"]["loss_one_device"], abs=cs.MESH_LOSS_ABS)


def test_main_refuses_without_tpu(capsys):
    assert cs.main([]) != 0
    out, err = capsys.readouterr()
    assert "no TPU" in err and '"ok"' not in out


def test_script_exits_nonzero_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], capture_output=True,
                          text=True, timeout=300, cwd=ROOT, env=env)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and '"ok"' not in proc.stdout


# ---------------------------------------------------------------------------
# tuner and measurement processes stay off the chip
def test_measurement_child_runs_on_cpu(monkeypatch):
    from repro.core import measure

    seen = {}

    def fake_run(cmd, env=None, **kw):
        seen["env"] = env
        return subprocess.CompletedProcess(cmd, 1, "", "")

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(measure.subprocess, "run", fake_run)
    with pytest.raises(RuntimeError):
        measure.measure_request({"arch": "granite-3-2b", "shape": "train_4k",
                                 "mesh": "single"})
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"


def test_dryrun_module_pins_cpu_before_jax():
    code = ("import os, sys\nos.environ['JAX_PLATFORMS'] = 'tpu'\n"
            "import repro.launch.dryrun\n"
            "assert 'jax' not in sys.modules\n"
            "print(os.environ['JAX_PLATFORMS'])\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120, env=env)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "cpu"


def test_tune_serve_daemon_defaults_to_cpu(monkeypatch, tmp_path):
    from repro.launch import tune_serve
    from repro.service import daemon

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(daemon, "TunerService", lambda *a, **k: None)
    monkeypatch.setattr(daemon, "serve_forever", lambda *a, **k: 0)
    assert tune_serve.main(["serve", "--store", str(tmp_path / "store"),
                            "--socket", str(tmp_path / "sock")]) == 0
    assert os.environ["JAX_PLATFORMS"] == "cpu"


# ---------------------------------------------------------------------------
def test_compile_cache_placement(monkeypatch):
    import jax

    from repro.launch import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
        assert compile_cache.enable_compile_cache() == "/placed/outside"
        assert jax.config.jax_compilation_cache_dir == prev  # left to JAX
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(ROOT, ".jax_cache")
        assert compile_cache.enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


# ---------------------------------------------------------------------------
# the launchers fail when they run nothing
def test_cli_entrypoints_fail_when_they_run_nothing(capsys, tmp_path,
                                                    no_compile_cache):
    from repro.launch.serve import main as serve_main
    from repro.launch.train import main as train_main

    argv = ["--arch", "granite-3-2b", "--smoke", "--steps", "2", "--batch", "2",
            "--seq", "16", "--ckpt-dir", str(tmp_path / "ckpt")]
    assert train_main(argv) == 0
    assert train_main(argv) == 1  # the checkpoint is already at step 2
    assert "took no step" in capsys.readouterr().err
    assert serve_main(["--arch", "qwen2-vl-72b", "--smoke"]) == 2
