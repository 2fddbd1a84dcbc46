#!/usr/bin/env python3
"""Readings that the limits of a cell's correctness check are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,... \
        --control-seeds 21,22,23 [--fault-seeds 31,32,33] \
        [--control <policy>]

The cell's runner (bench/<kind>_cell.py, by its traffic's kind) gives,
for each seed, the program's numbers against the plain reference (as a
run of the cell computes them); for each control seed the same numbers of
the control named in the cell's limits file (the program under a lower
precision policy, or the reference with its contractions' operands
rounded), or of the program under the policy --control gives; for each
fault seed those of the program with half of each batch left out.
Prints one JSON line per reading; runs nothing of the timed window's
measurement.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--control", default=None,
                    help="control policy in place of the limits file's")
    args = ap.parse_args()
    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    cell = harness.find_cell(args.workload)
    runner = harness.load_runner(cell.traffic["kind"])
    harness.device_info(cell.workload["chips"])
    harness.enable_compile_cache()
    control = {"policy": args.control} if args.control else None
    for row in runner.calibrate(cell, ints(args.seeds),
                                ints(args.control_seeds),
                                ints(args.fault_seeds), control):
        print("reading " + json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
