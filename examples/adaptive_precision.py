"""Closed-loop adaptive precision (DESIGN.md §9/§11): start the whole model
at 4-bit mantissas with the backward-weight GEMM four bits wider (the
policy "4; wgrad+4" — a per-GEMM-role width the pre-policy API could not
express), let the numerics observatory measure per-layer fidelity (SQNR,
mantissa clipping, flush-to-zero) on a telemetry cadence, and let the
hysteresis controller widen the layers that measurably need it — then
compare against the static-4-bit baseline the paper's fixed-format world
would have used.

    PYTHONPATH=src python examples/adaptive_precision.py [--steps 60]

Expected outcome (asserted): the controller widens at least one layer — on
this config the trigger is *measured clipping* (tile-saturation rate above
threshold at tile 24) and/or the SQNR floor — the adaptive run's final
loss is no worse than static 4-bit, and the telemetry snapshots record
BOTH policy widths (weight tap at the fwd width, gradient tap at the wgrad
width). The run writes results/numerics.json; render the per-layer table +
decision log with:

    PYTHONPATH=src python -m repro.analysis.report --numerics results/numerics.json

The adaptive run also streams a structured run-log (DESIGN.md §12) to
results/runlog.jsonl — step spans, progress lines, every telemetry
snapshot, the controller's widen decisions with their triggering signal,
and checkpoint saves. Tail it (live with --watch) via:

    PYTHONPATH=src python -m repro.analysis.report --follow results/runlog.jsonl
"""
import argparse
import json
import os
import shutil

import jax

from repro.configs import get_arch
from repro.core import HBFPConfig
from repro.data import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.numerics import ControllerConfig, PrecisionController, TapConfig
from repro.obs import JSONLSink, Recorder
from repro.optim import make_schedule
from repro.precision import parse_policy
from repro.train import init_train_state, make_step
from repro.train.trainer import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--cadence", type=int, default=5)
    ap.add_argument("--out", default="results/numerics.json")
    ap.add_argument("--runlog", default="results/runlog.jsonl")
    ap.add_argument("--ckpt", default="results/adaptive_ckpt")
    args = ap.parse_args()
    enable_compile_cache()

    arch = get_arch("yi-9b").smoke()
    # paper-fidelity tile 24: small tiles make mantissa clipping measurable
    base = HBFPConfig(4, 16, tile=24)
    policy = parse_policy("4; wgrad+4", base=base)
    pipe = SyntheticLM(arch.vocab_size, args.seq + 1, args.batch, seed=0)
    lrs = make_schedule("constant", base_lr=2e-3,
                        warmup_steps=max(args.steps // 20, 1),
                        total_steps=args.steps)

    # -- static 4-bit baseline (what a fixed-format run would do) --------
    static_step = make_step(arch, base, lrs)
    s = init_train_state(jax.random.key(0), arch, init_params)
    for i in range(args.steps):
        k = jax.random.fold_in(jax.random.key(0), i)
        s, m = static_step(s, pipe.batch(i), k)
    static_loss = float(m["loss"])
    print(f"static  {base.name}: final loss {static_loss:.4f}")

    # -- adaptive run: same seeds, per-role policy, controller in loop ----
    # structured run-log (DESIGN.md §12): every event the run produces —
    # step spans, snapshots, widen decisions, checkpoint saves — lands in
    # one JSONL stream `report.py --follow` can tail
    os.makedirs(os.path.dirname(args.runlog) or ".", exist_ok=True)
    rec = Recorder([JSONLSink(args.runlog, mode="w")])
    shutil.rmtree(args.ckpt, ignore_errors=True)  # fresh run, no resume
    ctrl = PrecisionController(ControllerConfig(patience=1, cooldown=1),
                               base_bits=4)
    step_fn = make_step(arch, policy, lrs, controller=ctrl,
                        tap=TapConfig(cadence=args.cadence), recorder=rec)
    trainer = Trainer(train_step=step_fn,
                      init_state=init_train_state(jax.random.key(0), arch,
                                                  init_params),
                      data_fn=pipe.batch, ckpt_dir=args.ckpt,
                      ckpt_every=max(args.steps // 2, 1), hbfp=policy,
                      controller=ctrl, recorder=rec, seed=0)
    state, metrics = trainer.run(args.steps, log_every=10)
    adaptive_loss = float(metrics["loss"])
    rec.close()

    widened = [d for d in ctrl.log if d["action"] == "widen"]
    clip_widened = [d for d in widened if d["reason"] == "clip>thr"]
    print(f"\nadaptive: final loss {adaptive_loss:.4f}  "
          f"({len(widened)} widen decisions, {len(clip_widened)} on "
          f"measured clipping; widths now {dict(ctrl.overrides())})")
    for d in ctrl.log:
        print(f"  step {d['step']:3d}  {d['action']:6s} {d['layer']:20s} "
              f"{d['from']:2d}->{d['to']:2d}  [{d['reason']}] "
              f"sqnr={d['sqnr_db']:.1f}dB clip={d['clip_frac']:.3f}")

    # both policy widths are observable in the taps (DESIGN.md §11): the
    # weight tap quantizes at the fwd width, the grad tap at the wgrad
    # width — every snapshot records them per tensor
    step0, snap0 = step_fn.buffer.history()[0]
    w_widths = set(snap0["widths"]["weights"].values())
    g_widths = set(snap0["widths"]["grads"].values())
    print(f"\ntap widths @ step {step0}: weights(fwd)={sorted(w_widths)} "
          f"grads(wgrad)={sorted(g_widths)}")
    assert w_widths == {4} and g_widths == {8}, (w_widths, g_widths)

    assert len(widened) >= 1, "controller never widened a layer"
    assert adaptive_loss <= static_loss + 1e-3, \
        (adaptive_loss, static_loss)
    print(f"adaptive <= static-4bit: "
          f"{adaptive_loss:.4f} <= {static_loss:.4f}  OK")

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    last = step_fn.buffer.latest()
    dump = {"step": None if last is None else last[0],
            "snapshot": None if last is None else last[1],
            "policy": policy.to_dict(),
            "controller": ctrl.to_meta(),
            "final_loss": {"adaptive": adaptive_loss,
                           "static_4bit": static_loss}}
    with open(args.out, "w") as f:
        json.dump(dump, f, indent=1)
    print(f"wrote {args.out} (render: python -m repro.analysis.report "
          f"--numerics {args.out})")
    print(f"wrote {args.runlog} (tail: python -m repro.analysis.report "
          f"--follow {args.runlog})")


if __name__ == "__main__":
    main()
