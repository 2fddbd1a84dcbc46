"""The control (the program under 4-bit mantissas, the precision below
the 8-bit one the cells state) comes out not correct, where the program
itself passes, at a size a test run holds."""
import calibrate
import tiny

CONTROL = dict(tiny.LIMITS, control={"policy": "4; backend=pallas"})


def failed(row, limits):
    return any(v > limits[k]["limit"] for k, v in row.items()
               if k in limits)


def test_training_control_fails_where_the_program_passes():
    rows = calibrate.train(tiny.cell(tiny.TRAIN, limits=CONTROL),
                           [2**34 + 9], [2**34 + 9], [])
    by = {r["kind"]: r for r in rows}
    assert not failed(by["program"], CONTROL)
    assert failed(by["control"], CONTROL)

