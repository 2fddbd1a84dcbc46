"""The system under test, as the configuration file describes it.

The program's own registered architecture (`program_arch` in the file)
gives the structure (dense pre-norm decoder, global causal attention,
SwiGLU); every number the file states replaces the registered one, so a
cell runs exactly the sizes and scales its file names.
"""
from __future__ import annotations

import dataclasses
import os
import sys

from harness import ROOT, SetupError

sys.path.insert(0, os.path.join(ROOT, "src"))


def program_arch(cfg: dict):
    """The program's ArchConfig for this configuration file."""
    from repro.configs import get_arch
    from reference import scales
    base = get_arch(cfg["program_arch"])
    emb, res, div = scales(cfg)
    arch = dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"],
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        dtype=cfg["torch_dtype"], emb_scale=emb, residual_scale=res,
        logit_divisor=div)
    structure = {"family": "dense", "attn_pattern": "global",
                 "ffn_act": "swiglu", "attn_softcap": None,
                 "final_softcap": None, "zero_centered_norm": False,
                 "post_norms": False, "n_experts": 0, "ssm": False,
                 "xlstm": False, "mrope": False, "input_kind": "tokens",
                 "n_codebooks": 1, "bfp_kv_cache": False}
    for k, v in structure.items():
        if getattr(arch, k) != v:
            raise SetupError(f"{cfg['program_arch']}: {k}={getattr(arch, k)!r}"
                             f" is not the dense decoder the reference "
                             f"implements ({v!r})")
    return arch
