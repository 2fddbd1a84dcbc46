"""The control, as each cell's limits file names it, comes out not
correct where the program itself passes, at a size a test run holds: for
the HBFP8 mix the program under 4-bit mantissas, the precision below its
8-bit ones; for the bf16 mix the plain reference with its contractions'
operands in fp8, the precision below bf16."""
import pytest

import tiny
import train_cell

MIXES = {"hbfp8": (tiny.TRAIN, dict(tiny.LIMITS, control={
             "policy": "4; backend=pallas"})),
         "bf16": (tiny.TRAIN_BF16, dict(tiny.LIMITS_BF16, control={
             "rounding": "fp8"}))}


def failed(row, limits):
    return any(v > limits[k]["limit"] for k, v in row.items()
               if k in limits)


@pytest.mark.parametrize("mix", MIXES)
def test_training_control_fails_where_the_program_passes(mix):
    traffic, limits = MIXES[mix]
    rows = list(train_cell.calibrate(
        tiny.cell(traffic, limits=limits), [2**34 + 9], [2**34 + 9], []))
    by = {r["kind"]: r for r in rows}
    assert not failed(by["program"], limits)
    assert failed(by["control"], limits)

