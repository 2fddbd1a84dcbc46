"""Fault-tolerant training loop.

Behaviours (exercised by tests/test_trainer.py):
  * auto-resume: on start, restores the latest valid checkpoint and resumes
    the data pipeline at the checkpointed step (pipeline is a pure function
    of step — bit-exact resume);
  * periodic checkpointing, atomic + optional background thread;
  * preemption simulation: `fail_at_step` raises mid-run, the next Trainer
    constructed over the same dir resumes losslessly;
  * elasticity: checkpoints are mesh-independent; restore accepts new
    shardings (node-loss → restart on a smaller/larger mesh);
  * straggler note: steps are synchronous SPMD — mitigation at this layer is
    restart-based (checkpoint elasticity) plus the data pipeline's
    statelessness; see README §fault-tolerance;
  * precision: `hbfp` may be a static HBFPConfig, a PrecisionSchedule, or
    a `precision.PrecisionPolicy` (pair with train.make_step — the step fn
    dispatches on state.step, so resume lands in the right policy segment
    automatically); the spec is stored in checkpoint meta and packed
    checkpoints use the per-layer widths resolved at the checkpointed step
    (DESIGN.md §8/§11);
  * adaptive precision (DESIGN.md §9): pass `controller=` (a
    `numerics.PrecisionController`, paired with `train.make_step(...,
    controller=...)`) — its full state incl. the decision log is
    serialized into checkpoint meta ("numerics_controller") and restored
    on resume, so a restarted run replays identical decisions;
  * observability (DESIGN.md §12): pass `recorder=` (an `obs.Recorder`)
    — every step runs inside a `"train/step"` span (synced via
    block_until_ready on log-cadence steps, dispatch-only otherwise)
    with two children, `"train/data"` (the batch and the step's key) and
    `"train/dispatch"` (the `train_step` call), progress lines become
    `"train/progress"` events (and the printed line is rendered from the
    same record), and checkpoint save/load events flow through to
    `repro.checkpoint`. Without one, the default is a sink-less recorder
    that emits nothing but annotates every span on the profiler's clock
    (`jax.profiler.TraceAnnotation("repro.train/step")`, ...), so a
    profiler trace of the loop names the host's work. All loop timing
    reads the recorder's *injected* clock, never `time.time()` directly,
    so tests drive a `ManualClock` and timing output is deterministic.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint
from repro.obs import Recorder
from repro.train.train_step import TrainState


class Trainer:
    def __init__(self, *, train_step: Callable, init_state: TrainState,
                 data_fn: Callable[[int], Any], ckpt_dir: Optional[str],
                 ckpt_every: int = 50, keep: int = 3,
                 hbfp=None,  # HBFPConfig | PrecisionSchedule | None
                 controller=None,  # numerics.PrecisionController | None
                 recorder=None,  # obs.Recorder | None (annotate only)
                 seed: int = 0, background_ckpt: bool = False,
                 state_shardings=None):
        self.train_step = train_step
        self.data_fn = data_fn
        self.recorder = recorder if recorder is not None else Recorder(
            annotate=jax.profiler.TraceAnnotation)
        if self.recorder.enabled and self.recorder.sync_fn is None:
            # spans around jitted work need a completion barrier; obs is
            # jax-free so the barrier is injected here (DESIGN.md §12)
            self.recorder.sync_fn = jax.block_until_ready
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.keep = keep
        self.hbfp = hbfp
        self.controller = controller
        self.seed = seed
        self.background_ckpt = background_ckpt
        self.state = init_state
        self.start_step = 0
        self._pending = None
        if ckpt_dir is not None and latest_step(ckpt_dir) is not None:
            self.state, meta = load_checkpoint(ckpt_dir, init_state,
                                               shardings=state_shardings,
                                               recorder=self.recorder)
            self.start_step = int(meta["step"])
            if controller is not None and "numerics_controller" in meta:
                controller.load_meta(meta["numerics_controller"])

    def _maybe_ckpt(self, step: int, force: bool = False):
        if self.ckpt_dir is None:
            return
        if force or (step > 0 and step % self.ckpt_every == 0):
            if self._pending is not None:
                self._pending.join()
                self._pending = None
            extra = None
            if self.controller is not None:
                extra = {"numerics_controller": self.controller.to_meta()}
            r = save_checkpoint(self.ckpt_dir, step, self.state,
                                hbfp=self.hbfp, keep=self.keep,
                                background=self.background_ckpt,
                                extra_meta=extra, recorder=self.recorder)
            if self.background_ckpt:
                self._pending = r

    def run(self, num_steps: int, *, fail_at_step: Optional[int] = None,
            log_every: int = 10, log_fn=print):
        """Run to global step `num_steps` (absolute, resume-aware)."""
        rec = self.recorder
        metrics = {}
        t0 = rec.clock.perf()
        for step in range(self.start_step, num_steps):
            if fail_at_step is not None and step == fail_at_step:
                raise RuntimeError(f"simulated preemption at step {step}")
            log_now = bool(log_every) and step % log_every == 0
            ljit = {}
            with rec.span("train/step", step=step) as sp:
                with rec.span("train/data", step=step):
                    batch = self.data_fn(step)
                    key = jax.random.fold_in(jax.random.key(self.seed), step)
                with rec.span("train/dispatch", step=step):
                    self.state, metrics = self.train_step(self.state, batch,
                                                          key)
                if log_now:
                    # scalars only (a taps-enabled step's "numerics" aux is
                    # a nested stats pytree — consumed upstream, skipped
                    # here). float() blocks on the step's outputs, so the
                    # span duration includes device time on log steps.
                    ljit = {k: float(v) for k, v in metrics.items()
                            if hasattr(v, "ndim") and v.ndim == 0
                            or isinstance(v, (int, float))}
                    sp.sync(self.state.params)
            if log_now:
                elapsed = rec.clock.perf() - t0
                rec.emit("train/progress", step=step, elapsed_s=elapsed,
                         **ljit)
                if log_fn is not None:
                    log_fn(f"step {step:6d} "
                           + " ".join(f"{k}={v:.4f}"
                                      for k, v in ljit.items())
                           + f" ({elapsed:.1f}s)")
            self._maybe_ckpt(step + 1)
        self._maybe_ckpt(num_steps, force=True)
        if self._pending is not None:
            self._pending.join()
            self._pending = None
        return self.state, metrics
