"""The paper's headline experiment (Fig. 3): HBFP is a drop-in replacement
for FP32 — same model, same hyperparameters, matching loss curves.

    PYTHONPATH=src python examples/hbfp_vs_fp32.py --steps 120
Prints an ASCII overlay of the fp32 / hbfp8_16 / hbfp12_16 training curves.
"""
import argparse

import jax

from repro.configs import get_arch
from repro.data import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.optim import make_schedule
from repro.precision import parse_policy
from repro.train import init_train_state, make_step


def train_curve(arch, policy, steps, pipe):
    sched = make_schedule("constant", base_lr=2e-3, warmup_steps=5,
                          total_steps=steps)
    step = make_step(arch, policy, sched)
    state = init_train_state(jax.random.key(0), arch, init_params)
    losses = []
    for i in range(steps):
        state, m = step(state, pipe.batch(i),
                        jax.random.fold_in(jax.random.key(1), i))
        losses.append(float(m["loss"]))
    return losses


def ascii_plot(curves, width=72, height=14):
    lo = min(min(c) for c in curves.values())
    hi = max(max(c) for c in curves.values())
    grid = [[" "] * width for _ in range(height)]
    marks = "ox+*"
    for ci, (name, c) in enumerate(curves.items()):
        n = len(c)
        for j in range(width):
            v = c[min(int(j / width * n), n - 1)]
            r = int((hi - v) / (hi - lo + 1e-9) * (height - 1))
            grid[r][j] = marks[ci % len(marks)]
    lines = [f"{hi:6.3f} +" + "".join(grid[0])]
    lines += ["       |" + "".join(row) for row in grid[1:-1]]
    lines += [f"{lo:6.3f} +" + "".join(grid[-1])]
    legend = "  ".join(f"{marks[i % len(marks)]}={n}"
                       for i, n in enumerate(curves))
    return "\n".join(lines) + "\n        " + legend


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--arch", default="yi-9b")
    args = ap.parse_args()
    enable_compile_cache()

    arch = get_arch(args.arch).smoke()
    pipe = SyntheticLM(arch.vocab_size, 33, 8, seed=11)
    curves = {}
    base24 = parse_policy("8").format().with_(tile=24)  # paper's FPGA tile
    for name, policy in (("fp32", parse_policy("fp32")),
                         ("hbfp8_16", parse_policy("8", base=base24)),
                         ("hbfp12_16", parse_policy("12", base=base24))):
        curves[name] = train_curve(arch, policy, args.steps, pipe)
        print(f"{name:10s} first={curves[name][0]:.4f} "
              f"last={curves[name][-1]:.4f}")
    print(ascii_plot(curves))
    gap8 = abs(curves["hbfp8_16"][-1] - curves["fp32"][-1])
    print(f"\nfinal-loss gap hbfp8_16 vs fp32: {gap8:.4f} "
          "(paper Fig. 3: curves overlap)")


if __name__ == "__main__":
    main()
