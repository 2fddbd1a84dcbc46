"""Weights and batches made from the run's seed, on the device.

Both the system under test and the plain reference read these: the
weights are the benchmark's, not the program's, so the reference takes
nothing that the program has made. The tree uses the program's parameter
names, stacked over layers ([L, ...] leaves), because that is the
interface through which the program takes weights; the structure module
(bench/structures/) says which leaves a model has.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import structures


def seed_key(seed: int):
    """A PRNG key for any whole number: JAX keeps only 32 bits of a seed,
    so the high bits are folded in rather than dropped."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def make_params(cfg: dict, key, dtype=jnp.bfloat16):
    """Random weights of the configuration's model at its sizes. Call
    under jit: one device program makes the whole tree."""
    return structures.of(cfg).make_params(cfg, key, dtype)


def train_batch(key, step, batch: int, seq: int, vocab: int):
    """The training rows of one step: uniform token ids over the
    vocabulary, inputs and next-token labels from one [B, S+1] draw, a
    different draw for every step."""
    t = jax.random.randint(jax.random.fold_in(key, step), (batch, seq + 1),
                           0, vocab, jnp.int32)
    return {"tokens": t[:, :-1], "labels": t[:, 1:]}
