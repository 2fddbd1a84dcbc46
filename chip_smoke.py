#!/usr/bin/env python3
"""Smoke run of HBFP training and serving on one TPU chip.

    python chip_smoke.py

Drives the main path once through its public entry points at yi-9b's
published widths (d_model 4096, d_ff 11008, 32 query / 4 KV heads,
head_dim 128), cut to 4 layers and to a vocabulary of 8000 (one eighth of
64000) so that the training state fits one TPU v5e's 16 GB of HBM.
Weights are random, made from a seed; data is the synthetic Markov stream.

  1. Training: a few steps of `train.make_step` + `Trainer` under policy
     "8" (simulated BFP) and "8; backend=pallas" (the fused Pallas kernels,
     flash attention included), and the step-0 loss of policy "fp32" on the
     same parameters and batch. Losses must be finite and fall, the three
     step-0 losses must agree within LOSS_TOL, and the compiled pallas step
     must hold every kernel as a `tpu_custom_call`.
  2. Serving: a paged `ServeEngine` at policy "8" answers requests of two
     prompt lengths. Every request must complete, and its first token must
     equal the argmax of a full forward over its prompt.

Everything runs in this one process, which holds the chip. The script exits
non-zero, and prints no result line, unless JAX's devices are TPUs. Its
last line is one JSON object, {"ok": true, "device": {...}}. This is a
smoke run, not a benchmark: it prints no throughput or utilisation.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.data import SyntheticLM  # noqa: E402
from repro.kernels import autotune  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import Ctx, forward, init_params  # noqa: E402
from repro.optim import make_schedule  # noqa: E402
from repro.precision import as_segment, parse_policy  # noqa: E402
from repro.serve.engine import ServeEngine  # noqa: E402
from repro.train import init_train_state, make_step  # noqa: E402
from repro.train.trainer import Trainer  # noqa: E402

# the smoke configuration: yi-9b widths, cut in depth and vocabulary
N_LAYERS, VOCAB = 4, 8000
BATCH, SEQ, STEPS, LR = 2, 2048, 6, 3e-4
PROMPT_LENS, PER_LEN, NEW_TOKENS, MAX_BATCH, CTX_LEN = (256, 1024), 4, 16, \
    4, 2048
# largest |step-0 loss difference| allowed between the sim, pallas and
# fp32 policies on the same parameters and batch, in nats
LOSS_TOL = 0.05

KERNELS = ("hbfp_matmul_fwd", "hbfp_matmul_dgrad", "hbfp_matmul_wgrad",
           "hbfp_flash_fwd", "hbfp_flash_dq", "hbfp_flash_dkv")
_CUSTOM_CALL = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*custom_call_target=\"tpu_custom_call\"",
    re.M)


def smoke_arch():
    """yi-9b at its published widths with depth and vocabulary cut; returns
    (arch, description of the cut)."""
    full = get_arch("yi-9b")
    arch = dataclasses.replace(full, n_layers=N_LAYERS, vocab_size=VOCAB)
    cut = (f"{arch.name}: d_model {arch.d_model}, d_ff {arch.d_ff}, heads "
           f"{arch.n_heads}/{arch.n_kv_heads}, head_dim {arch.hd} "
           f"(published); cut n_layers {full.n_layers}->{arch.n_layers}, "
           f"vocab {full.vocab_size}->{arch.vocab_size}; {arch.dtype}, "
           f"B={BATCH}, S={SEQ}")
    return arch, cut


def kernel_calls(hlo_text: str) -> dict:
    """Count the `tpu_custom_call`s of a compiled HLO module, in total and
    per named kernel of KERNELS."""
    names = _CUSTOM_CALL.findall(hlo_text)
    out = {"total": len(names)}
    for k in KERNELS:
        out[k] = sum(k in n for n in names)
    return out


def train_phase(arch, policy: str, *, batch: int, seq: int, steps: int,
                lr: float = LR, seed: int = 0) -> dict:
    """Train `steps` steps from a seeded init through make_step + Trainer
    (no checkpoints). Returns the per-step losses, the wall time of the
    run (compilation included) and the compiled step's kernel calls."""
    pol = parse_policy(policy, total_steps=steps)
    sched = make_schedule("constant", base_lr=lr, warmup_steps=1,
                          total_steps=steps)
    step_fn = make_step(arch, pol, sched, donate=True)
    losses = []

    def step(state, b, key):
        state, metrics = step_fn(state, b, key)
        losses.append(metrics["loss"])
        return state, metrics

    data = SyntheticLM(arch.vocab_size, seq + 1, batch, seed=seed)
    trainer = Trainer(train_step=step,
                      init_state=init_train_state(jax.random.key(seed), arch,
                                                  init_params),
                      data_fn=data.batch, ckpt_dir=None, hbfp=pol, seed=seed)
    t0 = time.perf_counter()
    state, _ = trainer.run(steps, log_every=0, log_fn=None)
    jax.block_until_ready(state)
    seconds = time.perf_counter() - t0
    (compiled_step,) = step_fn.variants.values()
    hlo = compiled_step.lower(state, data.batch(0),
                              jax.random.key(seed)).compile().as_text()
    return {"losses": [float(x) for x in losses], "seconds": seconds,
            "kernel_calls": kernel_calls(hlo)}


def serve_phase(arch, *, prompt_lens, per_len: int, new_tokens: int,
                max_batch: int, ctx_len: int, seed: int = 0) -> dict:
    """Serve per_len requests of each prompt length through a paged
    ServeEngine at policy "8", and recompute each first token as the argmax
    of a full forward over the prompt."""
    policy = parse_policy("8")
    engine = ServeEngine(arch, init_params(jax.random.key(seed), arch),
                         policy, max_batch=max_batch, ctx_len=ctx_len,
                         paged=True)
    prompts = {}
    for plen in prompt_lens:
        toks = jax.random.randint(jax.random.fold_in(
            jax.random.key(seed + 1), plen), (per_len, plen), 0,
            arch.vocab_size)
        for row in toks.tolist():
            prompts[engine.submit(row, max_new_tokens=new_tokens)] = row
    t0 = time.perf_counter()
    out = engine.drain()
    seconds = time.perf_counter() - t0
    ctx = Ctx(compute_dtype=jnp.dtype(arch.dtype),
              policy=as_segment(engine.hbfp))
    last_logits = jax.jit(lambda p, t: forward(
        p, {"tokens": t}, arch, ctx)[0][0, -1])
    complete = matched = 0
    for rid, prompt in prompts.items():
        toks = out.get(rid, [])
        complete += len(toks) == new_tokens
        ref = last_logits(engine.params, jnp.asarray([prompt], jnp.int32))
        matched += bool(toks) and toks[0] == int(jnp.argmax(ref))
    return {"requests": len(prompts), "complete": complete,
            "first_token_matches": matched, "seconds": seconds}


def check(train: dict, fp32_loss: float, serve: dict) -> list:
    """The smoke run's verdict: a list of failures (empty when all hold)."""
    fails = []
    for name, r in train.items():
        ls = r["losses"]
        if not all(math.isfinite(x) for x in ls):
            fails.append(f"{name}: non-finite loss {ls}")
        elif not ls[-1] < ls[0]:
            fails.append(f"{name}: loss did not fall {ls}")
    step0 = {name: r["losses"][0] for name, r in train.items()}
    step0["fp32"] = fp32_loss
    spread = max(step0.values()) - min(step0.values())
    if not spread <= LOSS_TOL:
        fails.append(f"step-0 losses {step0} differ by {spread} > {LOSS_TOL}")
    calls = train["pallas"]["kernel_calls"]
    missing = [k for k in KERNELS if not calls[k]]
    if missing:
        fails.append(f"pallas step lacks kernels {missing}: {calls}")
    if serve["complete"] != serve["requests"]:
        fails.append(f"serve: {serve['complete']}/{serve['requests']} "
                     f"requests complete")
    if serve["first_token_matches"] != serve["requests"]:
        fails.append(f"serve: {serve['first_token_matches']}/"
                     f"{serve['requests']} first tokens match the forward")
    return fails


def _hbm_line(dev) -> str:
    stats = dev.memory_stats() or {}
    return (f"hbm bytes_in_use={stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


def main() -> int:
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}")
    print(f"compile cache: {enable_compile_cache()}")
    # default tiles: an empty tuning table made here, never the
    # uncommitted results/autotune_kernels.json
    table = os.path.join(ROOT, "results", "chip_smoke_autotune.json")
    autotune.TuningTable({}, table).save()
    os.environ[autotune.TABLE_ENV] = table
    autotune.invalidate_cache()

    arch, cut = smoke_arch()
    print(f"config: {cut}")
    train = {}
    for name, policy in (("sim", "8"), ("pallas", "8; backend=pallas")):
        r = train[name] = train_phase(arch, policy, batch=BATCH, seq=SEQ,
                                      steps=STEPS)
        gc.collect()
        print(f"train[{policy}]: losses={r['losses']} "
              f"seconds={r['seconds']:.1f} "
              f"tpu_custom_calls={r['kernel_calls']}; {_hbm_line(dev)}")
    fp32 = train_phase(arch, "fp32", batch=BATCH, seq=SEQ, steps=1)
    gc.collect()
    print(f"train[fp32]: step-0 loss={fp32['losses'][0]}; {_hbm_line(dev)}")
    step0 = {k: r["losses"][0] for k, r in train.items()}
    print("step-0 agreement: " + " ".join(
        f"|{k}-fp32|={abs(v - fp32['losses'][0]):.6f}"
        for k, v in step0.items())
        + f" |sim-pallas|={abs(step0['sim'] - step0['pallas']):.6f}"
        + f" tol={LOSS_TOL}")

    serve = serve_phase(arch, prompt_lens=PROMPT_LENS, per_len=PER_LEN,
                        new_tokens=NEW_TOKENS, max_batch=MAX_BATCH,
                        ctx_len=CTX_LEN)
    print(f"serve[8, paged]: prompts {PROMPT_LENS} x {PER_LEN}, "
          f"{NEW_TOKENS} new tokens: {serve['complete']}/"
          f"{serve['requests']} complete, first tokens matching a full "
          f"forward {serve['first_token_matches']}/{serve['requests']} "
          f"(drain {serve['seconds']:.1f}s); {_hbm_line(dev)}")

    fails = check(train, fp32["losses"][0], serve)
    for f in fails:
        print(f"FAIL {f}", file=sys.stderr)
    if fails:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
