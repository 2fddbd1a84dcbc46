"""Operation and byte counts against hand-computed values."""
import json
import os

import pytest

import work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_yi_counts():
    c = cfg("yi-9b-4L")
    # per layer: q,o 4096x4096; k,v 4096x512; three FFN 4096x11008
    layer = 2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008
    assert layer == 173_015_040
    assert work.matmul_params(c) == 4 * layer + 4096 * 8000
    tokens = 2 * 2048
    attn = 4 * 2 * 32 * 2 * 2 * 128 * 2048 * 2049 // 2
    assert work.attention_fwd_ops(c, 2, 2048) == attn
    ops = work.train_step_ops(c, 2, 2048)
    assert ops == 6 * (4 * layer + 32_768_000) * tokens + 3 * attn
    assert ops == pytest.approx(18.64e12, rel=1e-3)
    assert 3 * attn / ops == pytest.approx(0.0443, abs=1e-3)


def test_minicpm_counts():
    c = cfg("minicpm-2b-10L")
    layer = 4 * 2304 * 2304 + 3 * 2304 * 5760
    assert layer == 61_046_784
    assert work.matmul_params(c) == 10 * layer + 2304 * 15344
    attn = 10 * 1 * 36 * 2 * 2 * 64 * 4096 * 4097 // 2
    ops = work.train_step_ops(c, 1, 4096)
    assert ops == 6 * (10 * layer + 2304 * 15344) * 4096 + 3 * attn
    assert ops == pytest.approx(18.19e12, rel=1e-3)
    assert 3 * attn / ops == pytest.approx(0.128, abs=2e-3)


def test_least_times_are_compute_bound_at_these_widths():
    c = cfg("yi-9b-4L")
    peak, bw = 393e12, 819e9
    gemm = work.gemm_least_seconds(c, 2, 2048, "int8", peak, bw)
    ops = 6 * work.matmul_params(c) * 4096
    # all compute-bound but the input gradients of wk and wv (N=512),
    # which write a [4096, 4096] bf16 result: 8 of them in 4 layers
    kv_ops = 2 * 4096 * 4096 * 512
    kv_bytes = 4096 * 512 + 4096 * 512 + 2 * 4096 * 4096
    assert kv_bytes / bw > kv_ops / peak
    extra = 8 * (kv_bytes / bw - kv_ops / peak)
    assert gemm == pytest.approx(ops / peak + extra, rel=1e-9)
    # bf16 operands: same operations at half the peak
    assert work.gemm_least_seconds(c, 2, 2048, "bf16", 197e12, bw) \
        == pytest.approx(ops / 197e12, rel=1e-9)
    att = work.attention_least_seconds(c, 2, 2048, "int8", peak, bw)
    assert att == pytest.approx(3 * work.attention_fwd_ops(c, 2, 2048)
                                / peak, rel=1e-9)


def test_least_time_is_memory_bound_for_a_thin_gemm():
    c = {"hidden_size": 64, "intermediate_size": 64,
         "num_attention_heads": 1, "num_key_value_heads": 1,
         "head_dim": 64, "num_hidden_layers": 1, "vocab_size": 64}
    # 8 GEMMs of [1, 64] x [64, 64], three passes each
    t = work.gemm_least_seconds(c, 1, 1, "int8", 1e15, 1e9)
    fwd = 64 + 64 * 64 + 2 * 64
    dgrad = 64 + 64 * 64 + 2 * 64
    wgrad = 64 + 64 + 2 * 64 * 64
    assert t == pytest.approx(8 * (fwd + dgrad + wgrad) / 1e9)
