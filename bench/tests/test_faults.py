"""A run whose timed path is broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a
run (build, checked steps or window, reference, comparison) at a tiny
size on the CPU, with one fault planted in the program: a step that
returns its state unchanged; half of each batch left out, the mean taken
over the rest. (The cells run on one chip, so no exchange between chips
can be left out, and they produce no token or answer to alter.)
"""
import pytest

import tiny
import train_cell

MIXES = {"hbfp8": (tiny.TRAIN, tiny.LIMITS),
         "bf16": (tiny.TRAIN_BF16, tiny.LIMITS_BF16)}


def correct(runner, cell, seed=2**35 + 1, seconds=1.0):
    out = runner.run(cell, seed, seconds)
    return all(c["ok"] for c in runner.finish(cell, seed, out))


def mix_cell(mix):
    traffic, limits = MIXES[mix]
    return tiny.cell(traffic, limits=limits)


@pytest.mark.parametrize("mix", MIXES)
def test_sound_training_run_is_correct(mix):
    assert correct(train_cell, mix_cell(mix))


@pytest.mark.parametrize("mix", MIXES)
def test_step_returning_its_state_unchanged_is_caught(monkeypatch, mix):
    real = train_cell.make_step

    def frozen(arch, policy, sched, donate=False, **kw):
        step = real(arch, policy, sched, donate=False, **kw)

        def run(state, batch, key):
            return state, step(state, batch, key)[1]
        return run

    monkeypatch.setattr(train_cell, "make_step", frozen)
    assert not correct(train_cell, mix_cell(mix))


@pytest.mark.parametrize("mix", MIXES)
def test_half_the_batch_left_out_is_caught(monkeypatch, mix):
    real = train_cell.train_batch
    monkeypatch.setattr(train_cell, "train_batch",
                        lambda *a: train_cell.halve(real(*a)))
    assert not correct(train_cell, mix_cell(mix))

