"""Operation and byte counts against counts made by hand."""
import json

import pytest

from chipbench import flops
from chipbench.tests.tiny import MOE, ROOT


def test_moe_gemm_counts():
    # (2, 3, 4) @ (2, 4, 5): 2*3*5 outputs of 4 multiply-adds each
    assert flops.moe_gemm(2, 3, 4, 5) == (2 * 2 * 3 * 5 * 4, 2 * (24 + 40 + 30))


def test_flash_attention_counts():
    # 4 causal queries see 1+2+3+4 = 10 keys; QK and PV are 2*D ops a pair
    # each, for each of 2 heads; q and o are 2*4*8, k and v 1*4*8 each
    assert flops.flash_attention(1, 2, 1, 4, 8) == (2 * 10 * 2 * (2 * 8), 2 * (64 + 64 + 32 + 32))


def test_matmul_params_tiny_moe():
    # per layer: q,o 64x64 each, k,v 64x32 each; router 64x4; 2 active
    # experts of 3 64x32 matrices; tied output 64x256
    per_layer = 2 * 64 * 64 + 2 * 64 * 32 + 64 * 4 + 2 * 3 * 64 * 32
    assert flops.matmul_params(MOE) == 2 * per_layer + 64 * 256


def test_step_flops_of_the_benchmark_configs():
    moe = json.loads((ROOT / "chipbench/configs/granite-moe-1b-a400m-8l.json").read_text())
    dense = json.loads((ROOT / "chipbench/configs/granite-3-2b.json").read_text())
    # granite-moe 8 layers: 176,425,984 matrix parameters a token passes
    # (8 x (3,145,728 attention + 32,768 router + 12,582,912 experts) +
    # 50,334,720 output); attention 4*8*16*64 per causal pair
    assert flops.matmul_params(moe["model"]) == 176_425_984
    per_tok = 3 * (2 * 176_425_984 + 4 * 8 * 16 * 64 * 4097 / 2)
    assert flops.train_flops_per_token(moe["model"], 4096) == pytest.approx(per_tok)
    # granite-3-2b: 40 x (10,485,760 attention + 50,331,648 MLP) + 100,669,440
    assert flops.matmul_params(dense["model"]) == 2_533_365_760
    fwd = 2 * 2_533_365_760 * 4096 + 4 * 40 * 32 * 64 * 4096 * 4097 // 2
    assert flops.forward_flops_per_seq(dense["model"], 4096) == fwd


def test_roofline_takes_the_slower_bound():
    pk = {"bf16_flops_s": 100.0, "hbm_bytes_s": 10.0}
    assert flops.roofline_s(1000, 50, pk) == 10.0  # compute-bound
    assert flops.roofline_s(100, 50, pk) == 5.0  # memory-bound


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks()  # the CPU is not in the peak table
