"""Weights and batches made from the run's seed, on the device.

Both the system under test and the plain reference read these: the
weights are the benchmark's, not the program's, so the reference takes
nothing that the program has made. The tree uses the program's parameter
names, stacked over layers ([L, ...] leaves), because that is the
interface through which the program takes weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key for any whole number: JAX keeps only 32 bits of a seed,
    so the high bits are folded in rather than dropped."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def make_params(cfg: dict, key, dtype=jnp.bfloat16):
    """Random weights of a dense pre-norm decoder at the configuration's
    sizes: normal, scaled by fan-in (0.02 for the embedding), norm scales
    at one. Call under jit: one device program makes the whole tree."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
    ks = jax.random.split(key, 9)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    layers = {
        "ln1_norm_scale": jnp.ones((L, D), jnp.float32),
        "ln2_norm_scale": jnp.ones((L, D), jnp.float32),
        "attn_wq": normal(ks[0], (L, D, H * hd), D),
        "attn_wk": normal(ks[1], (L, D, Hkv * hd), D),
        "attn_wv": normal(ks[2], (L, D, Hkv * hd), D),
        "attn_wo": normal(ks[3], (L, H * hd, D), H * hd),
        "ffn_wg": normal(ks[4], (L, D, F), D),
        "ffn_wi": normal(ks[5], (L, D, F), D),
        "ffn_wo": normal(ks[6], (L, F, D), F),
    }
    return {"layers": layers,
            "final_norm_scale": jnp.ones((D,), jnp.float32),
            "embed_table": normal(ks[7], (V, D), 2500.0),  # std 0.02
            "head_w": normal(ks[8], (D, V), D)}


def train_batch(key, step, batch: int, seq: int, vocab: int):
    """The training rows of one step: uniform token ids over the
    vocabulary, inputs and next-token labels from one [B, S+1] draw, a
    different draw for every step."""
    t = jax.random.randint(jax.random.fold_in(key, step), (batch, seq + 1),
                           0, vocab, jnp.int32)
    return {"tokens": t[:, :-1], "labels": t[:, 1:]}
