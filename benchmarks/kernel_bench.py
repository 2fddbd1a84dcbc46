"""Kernel microbenchmarks + tile autotuning (paper §5.3 units; DESIGN.md §10).

Two parts:

1. the original sim-vs-kernel wall-times (CPU: the jitted simulation path
   is the production path; the interpret-mode kernels are the correctness
   harness);
2. the tile autotuner (kernels/autotune.py) over the three training GEMMs
   (fwd / dgrad / wgrad): every candidate (bm, bk, bn) is timed against the
   default (128,128,128) tiling, the winners are persisted to the on-disk
   tuning table (results/autotune_kernels.json — `ops.py` reads it at
   trace time), and the default-vs-tuned speedups are recorded to
   BENCH_kernels.json at the repo root.

On the CPU container the kernels execute in interpret mode, where the cost
model is grid-step count × block work — large tiles win. On TPU the same
harness times real Mosaic executables and the VMEM-budget filter in
`autotune.candidates` matters; the recorded backend disambiguates.

    PYTHONPATH=src python benchmarks/kernel_bench.py [--smoke]

--smoke (the CI lane): a reduced shape and menu, nothing written to disk —
it exists to fail fast when a kernel or the autotuner regresses.
"""
import argparse
import json
import os

import jax

from benchmarks.common import timer
from repro.core import HBFP8_16, bfp
from repro.core.hbfp_ops import hbfp_matmul
from repro.kernels import autotune, ops

_OUT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_kernels.json")

# (M, K, N) and candidate menu per mode. Interpret-mode timing is python
# per grid step, so the full run keeps the menu to MXU-realistic sizes.
_FULL = {"shape": (512, 512, 512), "menu": (128, 256), "n": 2}
_SMOKE = {"shape": (128, 128, 128), "menu": (64, 128), "n": 1}


def _bench_sim(log, rows):
    log("# Kernel microbench (CPU)")
    x = jax.random.normal(jax.random.key(0), (512, 512))
    w = jax.random.normal(jax.random.key(1), (512, 512)) * 0.05

    f_fp32 = jax.jit(lambda x, w: x @ w)
    us = timer(f_fp32, x, w)
    rows.append(("matmul_fp32_512", us))
    log(f"  fp32 matmul 512^3          : {us:9.1f} us")

    f_sim = jax.jit(lambda x, w: hbfp_matmul(x, w, HBFP8_16))
    us_sim = timer(f_sim, x, w)
    rows.append(("hbfp_matmul_sim_512", us_sim))
    log(f"  hbfp8 matmul (sim path)    : {us_sim:9.1f} us "
        f"({us_sim / us:.2f}x fp32 — sim adds quantize ops; on TPU the "
        "fused int8 kernel is the fast path)")

    f_q = jax.jit(lambda x: bfp.quantize(x, 8, (1, None)))
    usq = timer(f_q, x)
    rows.append(("bfp_quantize_sim_512", usq))
    log(f"  bfp quantize 512x512 (sim) : {usq:9.1f} us")

    f_pack = jax.jit(lambda x: bfp.pack(x, 8, (128, 128)).mantissa)
    usp = timer(f_pack, x)
    rows.append(("bfp_pack_512", usp))
    log(f"  bfp pack (int8+exp)        : {usp:9.1f} us")


def _autotune_gemms(log, rows, *, shape, menu, n, table, save):
    M, K, N = shape
    x = jax.random.normal(jax.random.key(2), (M, K))
    w = jax.random.normal(jax.random.key(3), (K, N)) * 0.1
    g = jax.random.normal(jax.random.key(4), (M, N))

    runners = {
        "matmul_fwd": lambda t: ops.hbfp_matmul(
            x, w, mantissa_bits=8, bm=t[0], bk=t[1], bn=t[2]),
        "matmul_dgrad": lambda t: ops.hbfp_dgrad(
            g, w, mantissa_bits=8, bm=t[0], bk=t[1], bn=t[2]),
        "matmul_wgrad": lambda t: ops.hbfp_wgrad(
            x, g, mantissa_bits=8, bm=t[0], bk=t[1], bn=t[2]),
    }
    reports = {}
    log(f"# Autotune {M}x{K}x{N} m=8 (menu {menu}, "
        f"backend={jax.default_backend()}"
        f"{'-interpret' if ops.interpret() else ''})")
    for op, fn in runners.items():
        best, rep = autotune.autotune_op(op, fn, M, K, N, mantissa_bits=8,
                                         table=table, menu=menu, n=n,
                                         save=save)
        reports[op] = rep
        rows.append((f"{op}_tuned_us", rep["us"]))
        rows.append((f"{op}_speedup_vs_default", rep["speedup"]))
        log(f"  {op:13s}: default {rep['default_tiles']} "
            f"{rep['default_us']:9.1f} us -> tuned {rep['tiles']} "
            f"{rep['us']:9.1f} us ({rep['speedup']:.2f}x)")
    return reports


def run(log=print, smoke: bool = False):
    rows = []
    mode = _SMOKE if smoke else _FULL
    _bench_sim(log, rows)
    if smoke:
        # CI lane: in-memory table, nothing persisted
        table = autotune.TuningTable(path=os.devnull)
        reports = _autotune_gemms(log, rows, table=table, save=False, **mode)
        for op, rep in reports.items():
            # the default tiling is always in the candidate set, so the
            # winner can never be slower than it
            assert rep["speedup"] >= 1.0, (op, rep)
        # numeric gate: the tuned fwd winner must still match the oracle
        # exactly (a kernel regression fails here, not just a slow one)
        import numpy as np
        from repro.kernels import ref
        M, K, N = mode["shape"]
        x = jax.random.normal(jax.random.key(2), (M, K))
        w = jax.random.normal(jax.random.key(3), (K, N)) * 0.1
        t = reports["matmul_fwd"]["tiles"]
        y = ops.hbfp_matmul(x, w, mantissa_bits=8, bm=t[0], bk=t[1],
                            bn=t[2])
        yr = ref.hbfp_matmul_ref(x, w, mantissa_bits=8, bm=t[0], bk=t[1],
                                 bn=t[2])
        np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))
        log("smoke OK (tuned winner oracle-exact; no files written)")
        return rows
    table = autotune.get_table(refresh=True)
    reports = _autotune_gemms(log, rows, table=table, save=True, **mode)
    M, K, N = mode["shape"]
    record = {
        "backend": jax.default_backend()
        + ("-interpret" if ops.interpret() else ""),
        "shape": {"M": M, "K": K, "N": N},
        "mantissa_bits": 8,
        "menu": list(mode["menu"]),
        "ops": reports,
        "tuning_table": os.path.relpath(table.path,
                                        os.path.dirname(_OUT)),
        "note": "interpret-mode timings: cost ≈ grid steps × per-block "
                "python, so large tiles win; on TPU re-run to repopulate "
                "the table with Mosaic timings under the VMEM budget. "
                "speedup = default_us/us at the same shape.",
    }
    with open(_OUT, "w") as f:
        json.dump(record, f, indent=1)
    log(f"recorded -> {_OUT} (tuning table -> {table.path})")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced shape/menu, no files written (CI lane)")
    args = ap.parse_args()
    run(smoke=args.smoke)
