"""Accuracy-Boosters-style precision schedule, plus a per-GEMM-role width,
expressed as ONE `PrecisionPolicy` (DESIGN.md §11).

Most of the run trains with 4-bit mantissas (Harma et al., arXiv:2211.10737:
~99% of MACs), widening to 8- then 16-bit for the final stretch — while the
backward-weight GEMM (`wgrad`) runs two bits wider than the forward
throughout, the per-role axis the pre-policy API could not express. The
step function compiles once per distinct policy segment (three variants
here) and dispatches on the host step counter; the policy is stored in
checkpoint meta, so resume lands in the right segment automatically.

    PYTHONPATH=src python examples/precision_schedule.py [--steps 120]

Compare the loss trace against a static run (examples/train_lm.py
--precision 4): the staircase recovers most of the 4-bit gap by the time
it finishes wide.
"""
import argparse

import jax

from repro.configs import get_arch
from repro.data import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.optim import make_schedule
from repro.precision import QuantSite, parse_policy
from repro.train import init_train_state, make_step
from repro.train.trainer import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--ckpt-dir", default="/tmp/hbfp_sched_ckpt")
    args = ap.parse_args()
    enable_compile_cache()

    arch = get_arch("yi-9b").smoke()
    # 4-bit for the first ~85% of steps, widen 8 -> 16 at the end; wgrad
    # two bits wider than the forward in every segment
    policy = parse_policy("4@0,8@85%,16@95%; wgrad+2",
                          total_steps=args.steps)
    fwd0 = policy.resolve(QuantSite("layers", "fwd"), step=0)
    wg0 = policy.resolve(QuantSite("layers", "wgrad"), step=0)
    print(f"arch={arch.name} policy=[{policy.name}] "
          f"boundaries={policy.boundaries()} "
          f"step0: fwd={fwd0.mantissa_bits}b wgrad={wg0.mantissa_bits}b")

    pipe = SyntheticLM(arch.vocab_size, args.seq + 1, args.batch, seed=0)
    lrs = make_schedule("constant", base_lr=2e-3,
                        warmup_steps=max(args.steps // 20, 1),
                        total_steps=args.steps)
    step_fn = make_step(arch, policy, lrs)
    state = init_train_state(jax.random.key(0), arch, init_params)

    trainer = Trainer(train_step=step_fn, init_state=state,
                      data_fn=pipe.batch, ckpt_dir=args.ckpt_dir,
                      ckpt_every=50, hbfp=policy)
    if trainer.start_step:
        print(f"resumed at step {trainer.start_step} "
              f"(segment {policy.segment_index(trainer.start_step)})")
    state, metrics = trainer.run(args.steps, log_every=10)
    if metrics:
        print(f"final: loss={float(metrics['loss']):.4f} "
              f"mantissa_bits={int(float(metrics['mantissa_bits']))} "
              f"compiled_variants={len(step_fn.variants)}")
    else:  # checkpoint was already at/past --steps: nothing ran
        print(f"checkpoint already at step {trainer.start_step}; "
              f"nothing to do (raise --steps or clear {args.ckpt_dir})")


if __name__ == "__main__":
    main()
