"""End-to-end driver: train a transformer LM with HBFP on synthetic data,
with checkpointing/auto-resume — the full production loop at CPU scale.

    PYTHONPATH=src python examples/train_lm.py \
        --arch yi-9b --steps 300 --precision 8 [--full-size]

`--arch` accepts any of the 10 assigned architectures (reduced smoke config
by default; --full-size uses the published dims — only sensible on a real
cluster). `--precision` is a full policy spec (DESIGN.md §11): compare
against fp32 with --precision fp32, schedule with "4@0,8@90%", run the
backward-weight GEMM wider with "4; wgrad+4", or pin a layer with
"4; lm_head:8". The policy round-trips through checkpoint meta, so resume
picks it up automatically.
"""
import argparse

import jax

from repro.configs import arch_ids, get_arch
from repro.data import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.optim import make_schedule
from repro.precision import parse_policy
from repro.train import init_train_state, make_step
from repro.train.trainer import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b", choices=list(arch_ids()))
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--precision", default="8",
                    help='policy spec, e.g. "8", "fp32", "4@0,8@90%%", '
                         '"4; wgrad+4; lm_head:8; backend=pallas"')
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/hbfp_train_ckpt")
    ap.add_argument("--full-size", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    arch = get_arch(args.arch)
    if not args.full_size:
        arch = arch.smoke()
    policy = parse_policy(args.precision, total_steps=args.steps,
                          backend=arch.kernel_backend)
    print(f"arch={arch.name} params={arch.n_params()/1e6:.1f}M "
          f"policy=[{policy.name}]")

    pipe = SyntheticLM(arch.vocab_size, args.seq + 1, args.batch, seed=0)
    sched = make_schedule(arch.lr_schedule, base_lr=args.lr,
                          warmup_steps=max(args.steps // 20, 1),
                          total_steps=args.steps)
    step_fn = make_step(arch, policy, sched, donate=True)
    state = init_train_state(jax.random.key(0), arch, init_params)

    trainer = Trainer(train_step=step_fn, init_state=state,
                      data_fn=pipe.batch, ckpt_dir=args.ckpt_dir,
                      ckpt_every=100, hbfp=policy, background_ckpt=True)
    if trainer.start_step:
        print(f"resumed from checkpoint at step {trainer.start_step}")
    state, metrics = trainer.run(args.steps, log_every=25)
    print("final: " + ", ".join(f"{k}={float(v):.4f}"
                                for k, v in metrics.items()))


if __name__ == "__main__":
    main()
