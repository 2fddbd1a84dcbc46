#!/usr/bin/env python3
"""Readings that the limits of a cell's correctness check are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,... \
        --control-seeds 21,22,23 [--fault-seeds 31,32,33]

For each seed, the program's numbers against the plain reference (as a
run of the cell computes them); for each control seed the same numbers of
the control, the program under the lower-precision policy named in the
cell's limits file; for each fault seed those of the program with half of
each batch left out (the loss and gradients taken over the rest). Prints
one JSON line per reading; runs nothing of the timed window's
measurement.
"""
import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def emit(out, kind, seed, readings):
    """Print one reading and keep it in `out`."""
    row = {"kind": kind, "seed": seed, **readings}
    out.append(row)
    print("reading " + json.dumps(row), flush=True)


def halve(batch):
    """Half of the batch left out: the first half of the rows, or of the
    positions when there is one row."""
    t, l = batch["tokens"], batch["labels"]
    if t.shape[0] >= 2:
        n = t.shape[0] // 2
        return {"tokens": t[:n], "labels": l[:n]}
    n = t.shape[1] // 2
    return {"tokens": t[:, :n], "labels": l[:, :n]}


def train_program(cell, seed, policy=None, fault=False):
    import train_cell
    c = cell
    if policy is not None:
        c = harness.Cell(**dict(cell.__dict__,
                                traffic=dict(cell.traffic, policy=policy)))
    orig = train_cell.train_batch
    if fault:
        train_cell.train_batch = lambda *a: halve(orig(*a))
    try:
        trainer, losses = train_cell.build(c, seed)
        prog = train_cell.checked_steps(trainer, losses,
                                        cell.traffic["optimizer"]["b1"])
    finally:
        train_cell.train_batch = orig
    del trainer, losses
    gc.collect()
    return prog


def train(cell, seeds, control_seeds, fault_seeds):
    import compare
    import train_cell
    control = cell.limits["control"]["policy"]
    out = []
    for seed in dict.fromkeys(seeds + control_seeds + fault_seeds):
        ref = train_cell.reference_readings(cell, seed)
        gc.collect()
        runs = []
        if seed in seeds:
            runs.append(("program", train_program(cell, seed)))
        if seed in control_seeds:
            runs.append(("control", train_program(cell, seed, control)))
        if seed in fault_seeds:
            runs.append(("half_batch",
                         train_program(cell, seed, fault=True)))
        for kind, prog in runs:
            emit(out, kind, seed, compare.train_readings(prog, ref))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args()
    ints = lambda s: [int(x) for x in s.split(",") if x]
    cell = harness.find_cell(args.workload)
    harness.device_info(cell.workload["chips"])
    harness.enable_compile_cache()
    train(cell, ints(args.seeds), ints(args.control_seeds),
          ints(args.fault_seeds))


if __name__ == "__main__":
    main()
