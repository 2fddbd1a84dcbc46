#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is a `workloads` entry of BENCHMARK.json. The run makes its
weights and traffic from --seed, warms every shape it will use (set-up),
measures for --seconds, checks what the timed path produced against the
plain reference, and prints one JSON result line last on stdout. With
--trace 0 the metrics are the cell's end-to-end metrics; with --trace 1
they are its per-layer metrics, read from a profiler trace of a short
part of the window. Exits 2, printing no result, when JAX finds no TPU or
fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
import structures  # noqa: E402
from harness import log  # noqa: E402


class Tracer:
    """The profiler around part of the window, with a bench.window span
    marking what was traced."""

    def __init__(self, path: str):
        self.path = path

    def __enter__(self):
        import jax
        # no Python-function tracing: it slows the host code the window
        # measures; the benchmark's own spans name the host's work
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.path, profiler_options=opts)
        self.span = jax.profiler.TraceAnnotation("bench.window")
        self.span.__enter__()
        return self

    def __exit__(self, *exc):
        import jax
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        return False


class Reading:
    """What a metric's reader may read: the cell, the runner's output,
    the set-up time, the device's peaks and (traced runs) the trace."""

    def __init__(self, cell, out, setup_s, peak, trace=None):
        self.cell, self.out, self.setup_s = cell, out, setup_s
        self.peak, self.trace = peak, trace


def read_metrics(entries, reading) -> dict:
    out = {}
    for m in entries:
        v = harness.load_reader(m["name"])(reading)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = harness.find_cell(args.workload)
        structures.of(cell.config)
        runner = harness.load_runner(cell.traffic["kind"])
        device = harness.device_info(cell.workload["chips"])
        peak = harness.peak_of(cell.peaks, device["kind"])
    except (harness.SetupError, OSError, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    log(f"device: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    log(f"compile cache: {harness.enable_compile_cache()}")
    counter = harness.CompileCounter()
    cfg = cell.config
    log(f"config {cell.workload['config']}: {cfg['cut']}")
    tracer = None
    trace_dir = os.path.join(harness.ROOT, ".bench_trace",
                             args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracer = Tracer(trace_dir)
    marks = {}
    out = runner.run(cell, args.seed, args.seconds, tracer,
                     mark=lambda k: marks.setdefault(k, counter.compiles))
    setup_s = out["setup_end"] - T_START
    log(f"set-up {setup_s:.3f} s; compiles {marks.get('window', 0)} "
        f"(persistent cache hits {counter.hits}, misses {counter.misses});"
        f" compiles inside the window "
        f"{marks.get('window_end', 0) - marks.get('window', 0)}")
    log(f"peak_bytes_in_use {out['memory_peak_bytes']}")
    gc.collect()
    checks = runner.finish(cell, args.seed, out)
    trace = None
    if args.trace:
        import trace_reduce as trace_mod
        trace = trace_mod.load(trace_dir)
    reading = Reading(cell, out, setup_s, peak, trace)
    result = {"correct": all(c["ok"] for c in checks),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": read_metrics(
                  cell.per_layer if args.trace else cell.end_to_end,
                  reading),
              "device": dict(device,
                             memory_peak_bytes=out["memory_peak_bytes"])}
    if trace is not None:
        lo, hi = trace.window()
        result["device"]["busy_s"] = trace_mod.busy_ns(trace, lo, hi) / 1e9
        result["device"]["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {"device_ops": trace_mod.top_ops(trace),
                               "idle_gaps": trace_mod.idle_gaps(trace)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    result["checks"] = harness.checks_line(checks)
    harness.print_checks(checks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
