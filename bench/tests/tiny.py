"""Tiny cells for the CPU tests: the real runners at toy sizes."""
from __future__ import annotations

import copy

import harness

TINY_CFG = {
    "program_arch": "yi-9b", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "vocab_size": 256, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "torch_dtype": "bfloat16", "cut": "tiny"}

TINY_MINICPM = dict(TINY_CFG, program_arch="minicpm-2b",
                    num_key_value_heads=4, scale_emb=12, scale_depth=1.4,
                    scale_depth_layers=40, dim_model_base=16)

TRAIN = {"kind": "train", "policy": "8; backend=pallas", "arith": "int8",
         "batch": 2, "seq": 64, "trace_steps": 2,
         "optimizer": {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                       "weight_decay": 0.1, "grad_clip": 1.0}}

# set from CPU readings at these sizes over four seeds: the program read
# at most 0.0074 / 0.0099 / 0.0086 (loss / gradient / change); the 4-bit
# control at least 0.035 / 0.085 / 0.011; half batches at least
# 0.05 / 0.05 / 0.226.
LIMITS = {"loss_gap": {"limit": 0.02}, "grad_norm_gap": {"limit": 0.03},
          "change_norm_gap": {"limit": 0.05}}


# the bf16 mix: policy fp32, compute in the configuration's bfloat16
TRAIN_BF16 = dict(TRAIN, policy="fp32", arith="bf16")

# set from CPU readings at these sizes over four seeds: the program read
# at most 0.0027 / 0.0013 / 0.0010; the fp8 control (the reference with
# its contractions' operands in fp8) at least 0.014 / 0.019 / 0.0037;
# half batches (two seeds) at least 0.10 / 0.038 / 0.209.
LIMITS_BF16 = {"loss_gap": {"limit": 0.005},
               "grad_norm_gap": {"limit": 0.004},
               "change_norm_gap": {"limit": 0.003}}


def cell(traffic: dict, cfg: dict = TINY_CFG, limits: dict = LIMITS):
    return harness.Cell(
        workload={"name": "tiny." + traffic["kind"], "chips": 1},
        config=copy.deepcopy(cfg), traffic=copy.deepcopy(traffic),
        limits=copy.deepcopy(limits), end_to_end=[], per_layer=[],
        peaks={})
