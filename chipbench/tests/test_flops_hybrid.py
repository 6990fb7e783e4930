"""The hybrid's operation and byte counts against counts made by hand, and
the selective-scan roofline reader on an instruction's text."""
import json

import pytest

from chipbench import flops_hybrid, harness
from chipbench.tests.test_hybrid import HYBRID
from chipbench.tests.tiny import ROOT

PEAKS = {"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9, "chips": 1}


def test_matmul_params_tiny_hybrid():
    # d 64, d_inner 128, d_state 8, dt_rank 8: a Mamba mixer's in (64x256),
    # x (128x24), dt (8x128) and out (128x64) projections; attention q,o
    # 64x64 each and k,v 64x16 each; every layer's MLP 3 x 64x128; 2
    # attention and 26 Mamba layers; tied output 64x256
    mamba = 64 * 256 + 128 * 24 + 8 * 128 + 128 * 64
    attn = 2 * 64 * 64 + 2 * 64 * 16
    assert flops_hybrid.matmul_params(HYBRID) == (2 * attn + 26 * mamba + 28 * 3 * 64 * 128
                                                  + 64 * 256)


def test_forward_flops_of_the_cell():
    m = json.loads((ROOT / "chipbench/configs/jamba2-3b.json").read_text())["model"]
    # per Mamba layer 2560x10240 + 5120x192 + 160x5120 + 5120x2560 =
    # 40,079,360; per attention layer 2 x 2560x2560 + 2 x 2560x128 =
    # 13,762,560; per MLP 62,914,560; 26 Mamba, 2 attention, 28 MLPs and
    # the 2560x65536 output: 3,026,124,800 parameters a token passes
    assert flops_hybrid.matmul_params(m) == 3_026_124_800
    # attention: 4 x 2 layers x 20 heads x 128 per causal pair
    fwd = 2 * 3_026_124_800 * 8192 + 4 * 2 * 20 * 128 * 8192 * 8193 // 2
    assert flops_hybrid.forward_flops_per_seq(m, 8192) == fwd
    assert 6.1e9 < fwd / 8192 < 6.2e9


def test_selective_scan_counts():
    # (1, 2, 3) inputs, 4 state channels: 6 operations per (b, t, d, n);
    # u, dt, y 6 bf16 values each, B and C 8 each, A 12 and D 3 float32
    assert flops_hybrid.selective_scan(1, 2, 3, 4) == (6 * 24, 2 * (18 + 16) + 4 * 15)


def test_selective_scan_roofline_reads_the_instruction():
    text = ("%selective_scan.3 = bf16[1,8192,5120]{2,1,0} custom-call(bf16[1,8192,5120]{2,1,0} "
            "%a, bf16[1,8192,5120]{2,1,0} %b, f32[16,5120]{1,0} %c, bf16[1,8192,16]{2,1,0} %d, "
            "bf16[1,8192,16]{2,1,0} %e, f32[1,5120]{1,0} %f), custom_call_target=\"tpu_custom_call\"")
    ops, nbytes = flops_hybrid.selective_scan(1, 8192, 5120, 16)
    least = max(ops / PEAKS["bf16_flops_s"], nbytes / PEAKS["hbm_bytes_s"])
    reader = harness.load_module(harness.HERE / "metrics" / "selective_scan_roofline.py")
    got = reader.read({"trace": {"kernels": {"selective_scan": [(text, 0.01), (text, 0.03)]}},
                       "peaks": PEAKS})
    assert got == pytest.approx(100.0 * 2 * least / 0.04)
    assert reader.read({"trace": {"kernels": {}}, "peaks": PEAKS}) is None
    assert reader.read({"trace": None, "peaks": PEAKS}) is None
