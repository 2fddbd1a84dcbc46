"""Training cell runner: `train.make_step` through `Trainer`.

Set-up builds one Trainer (the compiled step and its state), drives it
from the seed through the first CHECKED steps with the window's own call
and feed, and reads what the comparison needs: each step's loss, the
first gradient as the optimizer got it (its first moment over 1 - b1),
and the parameters' change over those steps. The window then goes on from
the same Trainer. After the window the program's state is freed and the
plain reference runs the same steps. `calibrate` gives the readings the
cell's limits are set from (bench/calibrate.py).
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import math
from time import perf_counter

import jax
import numpy as np
from jax.profiler import TraceAnnotation

import compare
import reference
import weights
from harness import log, memory_peak_bytes
from program import program_arch
from repro.optim import make_schedule
from repro.precision import parse_policy
from repro.train import init_train_state, make_step
from repro.train.trainer import Trainer
from weights import make_params, seed_key, train_batch

CHECKED = 3
# seconds of steps dispatched ahead of the one the window waits for
AHEAD_S = 6.0


def build(cell, seed: int, rows=None):
    """The program's Trainer at the cell's sizes, from the seed, and the
    list its step appends each loss to. `rows` makes a step's batch
    (train_batch unless given)."""
    cfg, tr = cell.config, cell.traffic
    arch = program_arch(cfg)
    hp = tr["optimizer"]
    wkey, dkey = jax.random.split(seed_key(seed))
    pol = parse_policy(tr["policy"])
    sched = make_schedule("constant", base_lr=hp["lr"], warmup_steps=1,
                          total_steps=1)
    step_fn = make_step(arch, pol, sched, donate=True,
                        weight_decay=hp["weight_decay"],
                        grad_clip=hp["grad_clip"])
    losses = []

    def step(state, batch, key):
        state, metrics = step_fn(state, batch, key)
        losses.append(metrics["loss"])
        return state, metrics

    state = jax.jit(lambda k: init_train_state(
        k, arch, lambda kk, _: make_params(cfg, kk)))(wkey)
    B, S, V = tr["batch"], tr["seq"], cfg["vocab_size"]
    make = jax.jit(rows or train_batch, static_argnums=(2, 3, 4))
    trainer = Trainer(train_step=step, init_state=state,
                      data_fn=lambda s: make(dkey, s, B, S, V),
                      ckpt_dir=None, hbfp=pol, seed=seed & 0x7FFFFFFF)
    return trainer, losses


def run_steps(trainer, first: int, n: int):
    """Steps first .. first+n-1 through the Trainer's own loop."""
    trainer.start_step = first
    trainer.run(first + n, log_every=0, log_fn=None)


_norms = jax.jit(reference.leaf_norms)


def checked_steps(trainer, losses, b1: float) -> dict:
    """Run the CHECKED steps and read the program's side of the check."""
    p0 = jax.device_get(trainer.state.params)
    run_steps(trainer, 0, 1)
    grad1 = {k: v / (1 - b1) for k, v in compare.flat(
        jax.device_get(_norms(trainer.state.opt.mu))).items()}
    run_steps(trainer, 1, CHECKED - 1)
    p3 = jax.device_get(trainer.state.params)
    change = compare.flat(jax.tree.map(
        lambda a, b: float(np.linalg.norm(
            (np.asarray(a, np.float64) - np.asarray(b, np.float64)).ravel())),
        p3, p0))
    del p0, p3
    return {"losses": [float(x) for x in losses[:CHECKED]], "grad1": grad1,
            "change": change}


def window(trainer, losses, first: int, done):
    """Steps from `first` until done(steps, seconds) says so. About
    AHEAD_S seconds of steps, by the rate of those finished so far, stay
    dispatched ahead of the one waited for, so the chip stays fed while
    the host stands still. Then nothing more is sent, and every sent step
    finishes inside the window. Returns (steps, window seconds)."""
    t0 = perf_counter()
    n = first
    sent = collections.deque()
    finished = 0
    longest, at, last = 0.0, 0.0, t0
    while True:
        with TraceAnnotation("bench.step_dispatch"):
            run_steps(trainer, n, 1)
        n += 1
        sent.append(losses[-1])
        ahead = max(1, int(AHEAD_S * finished / (perf_counter() - t0)))
        while len(sent) > ahead:
            with TraceAnnotation("bench.sync"):
                jax.block_until_ready(sent.popleft())
            finished += 1
        now = perf_counter()
        if now - last > longest:
            longest, at = now - last, last - t0
        last = now
        if done(n - first, now - t0):
            break
    with TraceAnnotation("bench.sync"):
        jax.block_until_ready(trainer.state)
    win = perf_counter() - t0
    log(f"window: {n - first} steps in {win:.3f} s, {ahead} ahead at the "
        f"end; longest pass of the host's loop {longest:.3f} s, from "
        f"{at:.3f} s")
    return n - first, win


def reference_readings(cell, seed: int, rounding=None) -> dict:
    cfg, tr = cell.config, cell.traffic
    wkey, dkey = jax.random.split(seed_key(seed))
    params0 = jax.device_get(jax.jit(lambda k: make_params(cfg, k))(wkey))
    batches = [weights.train_batch(dkey, s, tr["batch"], tr["seq"],
                                   cfg["vocab_size"])
               for s in range(CHECKED)]
    r = reference.train_readings(cfg, tr["optimizer"], params0, batches,
                                 rounding)
    return {"losses": r["losses"], "grad1": compare.flat(r["grad1"]),
            "grad1_raw": compare.flat(r["grad1_raw"]),
            "change": compare.flat(r["change"])}


def run(cell, seed: int, seconds: float, tracer=None,
        mark=lambda _: None) -> dict:
    """One run of a training cell. With a `tracer`, the window is the
    mix's trace_steps steps, all traced. `mark` is called with "window"
    and "window_end" at the window's edges."""
    tr = cell.traffic
    trainer, losses = build(cell, seed)
    prog = checked_steps(trainer, losses, tr["optimizer"]["b1"])
    log(f"checked steps: losses {prog['losses']}")
    setup_end = perf_counter()
    mark("window")
    if tracer is None:
        steps, win = window(trainer, losses, CHECKED,
                            lambda n, t: t >= seconds)
    else:
        with tracer:
            steps, win = window(trainer, losses, CHECKED,
                                lambda n, t: n >= tr["trace_steps"])
    mark("window_end")
    window_losses = [float(x) for x in losses[CHECKED:]]
    failed = sum(not math.isfinite(x) for x in window_losses)
    out = {"setup_end": setup_end, "steps": steps, "window_s": win,
           "tokens": steps * tr["batch"] * tr["seq"],
           "attempted": steps, "failed": failed,
           "memory_peak_bytes": memory_peak_bytes(1), "prog": prog}
    del trainer, losses
    gc.collect()
    return out


def finish(cell, seed: int, out: dict) -> list:
    """After the window: the reference and the comparison."""
    ref = reference_readings(cell, seed)
    log(f"reference losses {ref['losses']}")
    return compare.train_checks(out["prog"], ref, cell.limits)


def halve(batch):
    """Half of the batch left out: the first half of the rows, or of the
    positions when there is one row."""
    t, l = batch["tokens"], batch["labels"]
    if t.shape[0] >= 2:
        n = t.shape[0] // 2
        return {"tokens": t[:n], "labels": l[:n]}
    n = t.shape[1] // 2
    return {"tokens": t[:, :n], "labels": l[:, :n]}


def calibration_program(cell, seed: int, policy=None, fault=False) -> dict:
    """The program's side of the check, under `policy` in place of the
    mix's, or with half of each batch left out (`fault`)."""
    if policy is not None:
        cell = dataclasses.replace(
            cell, traffic=dict(cell.traffic, policy=policy))
    rows = (lambda *a: halve(train_batch(*a))) if fault else None
    trainer, losses = build(cell, seed, rows)
    prog = checked_steps(trainer, losses, cell.traffic["optimizer"]["b1"])
    del trainer, losses
    gc.collect()
    return prog


def control_readings(cell, seed: int, control: dict) -> dict:
    """The control's side of the check: the program under the control's
    `policy`, or the plain reference with its contractions' operands
    rounded to `rounding` put in the program's place."""
    if "rounding" in control:
        return reference_readings(cell, seed, control["rounding"])
    return calibration_program(cell, seed, control["policy"])


def calibrate(cell, seeds, control_seeds, fault_seeds, control=None):
    """Yield one row of readings (kind, seed, the numbers compared) for
    the program on each seed, for the control (by default the one the
    limits file names) on each control seed, and for half of each batch
    left out on each fault seed."""
    control = control or cell.limits["control"]
    for seed in dict.fromkeys(list(seeds) + list(control_seeds)
                              + list(fault_seeds)):
        ref = reference_readings(cell, seed)
        gc.collect()
        runs = []
        if seed in seeds:
            runs.append(("program", calibration_program(cell, seed)))
        if seed in control_seeds:
            runs.append(("control", control_readings(cell, seed, control)))
        if seed in fault_seeds:
            runs.append(("half_batch",
                         calibration_program(cell, seed, fault=True)))
        for kind, prog in runs:
            yield {"kind": kind, "seed": seed,
                   **compare.train_readings(prog, ref)}
