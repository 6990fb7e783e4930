"""Tiny versions of the benchmark's cells for CPU tests: the same drivers,
configuration keys and traffic keys at widths a test run can hold."""
from __future__ import annotations

import copy
import json
import pathlib
import time

from chipbench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]

MOE = {"hidden_size": 64, "intermediate_size": 32, "num_hidden_layers": 2,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
       "num_local_experts": 4, "num_experts_per_tok": 2, "vocab_size": 256,
       "rope_theta": 10000.0, "rms_norm_eps": 1e-06, "tie_word_embeddings": True,
       "hidden_act": "silu", "dtype": "float32", "moe_capacity_factor": 1.25,
       "moe_capacity_round": 128}
DENSE = {k: v for k, v in MOE.items() if not k.startswith(("num_local", "num_experts",
                                                           "moe_"))}
DENSE["intermediate_size"] = 128
PROGRAM = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
           "vocab_size": 256}


def config(model: dict) -> dict:
    moe = "num_local_experts" in model
    over = dict(PROGRAM, d_ff=model["intermediate_size"], dtype=model["dtype"])
    if moe:
        over.update(n_experts=model["num_local_experts"],
                    experts_per_token=model["num_experts_per_tok"])
    arch = "granite-moe-1b-a400m" if moe else "granite-3-2b"
    return {"model": model, "program": {"arch": arch, "overrides": over}}


TRAFFIC = {
    "train": {"driver": "train", "batch": 1, "seq_len": 96,
              "tokens": {"dist": "zipf", "exponent": 1.0},
              "plan": {"tune": {"arch": "granite-moe-1b-a400m", "shape": "train_4k",
                                "algo": "mcts_1s", "seed": 0}},
              "optimizer": {"peak_lr": 3e-4, "warmup_steps": 0, "total_steps": 100000,
                            "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
                            "clip_norm": 1.0},
              "checked_steps": 3, "chunk_steps": 2},
    "prefill": {"driver": "prefill", "batch": 2, "seq_len": 64,
                "tokens": {"dist": "zipf", "exponent": 1.0},
                "plan": {"tune": {"arch": "granite-3-2b", "shape": "train_4k",
                                  "algo": "mcts_1s", "seed": 0}}},
    "serve": {"driver": "serve", "slots": 4, "max_len": 64, "clients": 8, "think_s": 0,
              "prompt_len": {"dist": "lognormal", "median": 8, "sigma": 0.8, "min": 2,
                             "max": 24},
              "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.8, "min": 2,
                             "max": 24},
              "quantiles": 8, "order_seed": 7, "warm_prompt_len": 2,
              "tokens": {"dist": "zipf", "exponent": 1.0},
              "plan": {"tune": {"arch": "granite-3-2b", "shape": "decode_32k",
                                "algo": "mcts_1s", "seed": 0}},
              "check_requests": 4},
}
LIMITS = {"train": {"grad_gap": 1e-3, "grad_dir_gap": 1e-5, "change_gap": 1e-3},
          "prefill": {"logit_rel_err": 1e-4},
          "serve": {"served_logit_gap": 1e-4}}
CELL = {"train": "moe_train_4k", "prefill": "dense_prefill_4k", "serve": "dense_serve_alpaca"}


def make_run(kind: str, seed: int = 12345678901, seconds: float = 0.5,
             model: dict | None = None) -> harness.Run:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == CELL[kind])
    model = copy.deepcopy(model or (MOE if kind == "train" else DENSE))
    return harness.Run(bench, cell, config(model), copy.deepcopy(TRAFFIC[kind]),
                       dict(LIMITS[kind]), seed, seconds, False, time.perf_counter())
