"""The trace reduction on a small synthetic trace with known answers."""
import pytest

import trace_reduce as T


def make_trace():
    ms = 1_000_000
    ops = [("%while.3 = (s32[]) while(...)", 0 * ms, 50 * ms),
           ("%hbfp_matmul_fwd.12 = f32[8,8] custom-call(...)", 0, 10 * ms),
           ("%hbfp_matmul_wgrad.2 = f32[8,8] custom-call(...)",
            10 * ms, 25 * ms),
           ("%hbfp_flash_fwd.1 = bf16[4,8] custom-call(...)",
            30 * ms, 40 * ms),
           ("%fusion.7 = f32[8] fusion(...)", 60 * ms, 70 * ms),
           ("%hbfp_matmul_fwd.13 = f32[8,8] custom-call(...)",
            80 * ms, 90 * ms)]
    modules = [("jit_train_step(123)", 0, 50 * ms),
               ("jit_train_step(123)", 60 * ms, 95 * ms),
               ("jit__lambda(9)", 96 * ms, 97 * ms)]
    spans = [("bench.window", -5 * ms, 100 * ms),
             ("bench.sync", 50 * ms, 60 * ms),
             ("bench.step_dispatch", 90 * ms, 100 * ms)]
    return T.Trace(ops={"/device:TPU:0": ops},
                   modules={"/device:TPU:0": modules},
                   spans=spans)


def test_busy_is_the_union_of_nested_ops():
    tr = make_trace()
    lo, hi = tr.window()
    # [0, 50] (loop covering its body) + [60, 70] + [80, 90]
    assert T.busy_ns(tr, lo, hi) == pytest.approx(70e6)
    assert T.busy_ns(tr, 5e6, 65e6) == pytest.approx(50e6)


def test_kernel_seconds_sum_by_name_prefix():
    tr = make_trace()
    assert T.op_seconds(tr, "hbfp_matmul_") == pytest.approx(0.035)
    assert T.op_seconds(tr, "hbfp_flash_") == pytest.approx(0.010)
    assert T.op_seconds(tr, "nothing_") == 0.0


def test_modules_and_top_ops_leave_out_loops():
    tr = make_trace()
    assert len(T.module_events(tr, "jit_train_step")) == 2
    top = T.top_ops(tr, n=3)
    assert [k for k, _ in top] == ["hbfp_matmul_wgrad.2",
                                   "hbfp_matmul_fwd.12", "hbfp_flash_fwd.1"]
    assert top[0][1] == pytest.approx(0.015)


def test_idle_gaps_are_labelled_by_the_host():
    tr = make_trace()
    gaps = T.idle_gaps(tr)
    secs = sorted(round(s * 1e3, 6) for _, s in gaps)
    assert secs == [5.0, 10.0, 10.0, 10.0]      # -5..0, 50..60, 70..80, 90..100
    labels = sorted(lab for lab, _ in gaps)
    # -5..0 and 70..80 lie in the window span alone
    assert labels == ["bench.step_dispatch", "bench.sync", "bench.window",
                      "bench.window"]


def test_instruction_names():
    assert T.instr_name("%hbfp_flash_dkv.10 = (bf16[36]) custom-call(x)") \
        == "hbfp_flash_dkv.10"
    assert T.base_name("hbfp_flash_dkv.10") == "hbfp_flash_dkv"


def test_a_recorded_trace_loads_with_its_window(tmp_path):
    import jax
    import jax.numpy as jnp

    import run
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with run.Tracer(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.sync"):
            f(x).block_until_ready()
    tr = T.load(str(tmp_path))
    lo, hi = tr.window()
    assert hi > lo
    assert any(s[0] == "bench.sync" and lo <= s[1] <= s[2] <= hi
               for s in tr.spans)
