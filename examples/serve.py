"""Serve a model with batched requests: prefill + token-by-token decode
with narrow-BFP weights (the paper's inference-density configuration).

    PYTHONPATH=src python examples/serve.py --arch gemma2-2b --batch 4
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import arch_ids, get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params, make_cache
from repro.precision import parse_policy
from repro.train.serve_step import (make_decode_fn, make_prefill_fn,
                                    narrow_serving_params,
                                    prefill_to_decode_cache)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b", choices=list(arch_ids()))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--gen-len", type=int, default=20)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--precision", default="8",
                    help='serving policy spec, e.g. "8", "8; lm_head:12"')
    args = ap.parse_args()
    enable_compile_cache()

    arch = get_arch(args.arch).smoke()
    if arch.input_kind != "tokens" or arch.n_codebooks > 1:
        raise SystemExit("this demo serves token-in/token-out archs")
    B, P, G = args.batch, args.prompt_len, args.gen_len

    # load + narrow once (paper: weights stored/served in narrow BFP);
    # the serving policy resolves per-layer widths at load time
    policy = parse_policy(args.precision)
    params = narrow_serving_params(
        init_params(jax.random.key(0), arch), arch, policy)
    prefill_fn = jax.jit(make_prefill_fn(arch, policy))
    decode_fn = jax.jit(make_decode_fn(arch, policy))

    prompts = jax.random.randint(jax.random.key(1), (B, P), 0,
                                 arch.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32)[None], (B, P))

    t0 = time.perf_counter()
    logits, cache = prefill_fn(params, {"tokens": prompts,
                                        "positions": pos})
    cache = prefill_to_decode_cache(cache, arch, P + G)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    key = jax.random.key(2)
    tok = logits[:, -1].argmax(-1)[:, None].astype(jnp.int32)
    out = [tok]
    t0 = time.perf_counter()
    for t in range(G - 1):
        p = jnp.full((B, 1), P + t, jnp.int32)
        logits, cache = decode_fn(params, {"tokens": tok, "positions": p},
                                  cache)
        key, sub = jax.random.split(key)
        tok = jax.random.categorical(
            sub, logits[:, 0] / args.temperature)[:, None].astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.perf_counter() - t0

    gen = jnp.concatenate(out, axis=1)
    print(f"arch={arch.name} batch={B} prompt={P} gen={G}")
    print(f"prefill: {t_prefill*1e3:.1f} ms   decode: "
          f"{t_decode/max(G-1,1)*1e3:.1f} ms/token (CPU, jitted)")
    for i in range(min(B, 2)):
        print(f"  req{i}: prompt={prompts[i].tolist()} -> "
              f"gen={gen[i].tolist()}")


if __name__ == "__main__":
    main()
