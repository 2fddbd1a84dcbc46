"""Plain float32 reference of the configuration's model, its loss, and
AdamW.

The model itself is the structure module's (bench/structures/): it
follows the published description and imports nothing of the program
under test. Every contraction runs at `Precision.HIGHEST`
(`structures.mm`), so a TPU keeps float32 accuracy. The optimizer and
the readings the comparison takes are shared by every structure.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

import structures
from structures import F32


def logits(cfg: dict, params, tokens):
    """f32 logits [B, S, V] of token ids [B, S]."""
    return structures.of(cfg).logits(cfg, params, tokens)


def loss(cfg: dict, params, batch):
    """Mean next-token cross-entropy over every row and position."""
    return structures.of(cfg).loss(cfg, params, batch)


def leaf_norms(tree):
    """Euclidean norm of every leaf, as a tree of f32 scalars."""
    return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(F32)))), tree)


@functools.partial(jax.jit, static_argnums=(0, 1, 2),
                   donate_argnums=(3, 4, 5))
def adamw_step(cfg_json, hp_items, rounding, params, mu, nu, batch, t):
    """One f32 AdamW step with global-norm clipping, its contractions'
    operands rounded to `rounding` (structures.rounded). Returns (params,
    mu, nu, loss, clipped-gradient leaf norms, raw-gradient leaf norms)."""
    cfg, hp = json.loads(cfg_json), dict(hp_items)
    with structures.rounded(rounding):
        val, g = jax.value_and_grad(lambda p: loss(cfg, p, batch))(params)
    raw = leaf_norms(g)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    g = jax.tree.map(
        lambda x: x * jnp.minimum(1.0, hp["grad_clip"] / (gnorm + 1e-9)), g)
    b1, b2, eps = hp["b1"], hp["b2"], hp["eps"]
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree.map(lambda n, x: b2 * n + (1 - b2) * x * x, nu, g)
    tf = t.astype(F32)
    bc1, bc2 = 1 - b1 ** tf, 1 - b2 ** tf

    def upd(p, m, n):
        u = (m / bc1) / (jnp.sqrt(n / bc2) + eps)
        if p.ndim >= 2:  # decoupled decay on every leaf stored as a matrix
            u = u + hp["weight_decay"] * p
        return p - hp["lr"] * u

    params = jax.tree.map(upd, params, mu, nu)
    return params, mu, nu, val, leaf_norms(g), raw


def train_readings(cfg: dict, hp: dict, params0, batches, rounding=None):
    """Run len(batches) reference steps from params0 (a tree of host
    arrays, any float dtype), with the contractions' operands rounded to
    `rounding` (None: float32). Returns the losses, the first step's
    clipped and raw gradient leaf norms, and the leaf norms of the
    parameters' change over all steps (taken on the host, in float64)."""
    # the whole file, nested groups and nulls included, as a static string
    items = json.dumps(cfg, sort_keys=True)
    hp_items = tuple(sorted(hp.items()))
    params = jax.tree.map(lambda a: jnp.asarray(a, F32), params0)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, g1, raw1 = [], None, None
    for i, b in enumerate(batches):
        params, mu, nu, val, gn, raw = adamw_step(
            items, hp_items, rounding, params, mu, nu, b,
            jnp.int32(i + 1))
        losses.append(float(val))
        if i == 0:
            g1, raw1 = jax.device_get(gn), jax.device_get(raw)
    del mu, nu
    change = jax.tree.map(
        lambda new, old: float(np.linalg.norm(
            (np.asarray(new, np.float64) - np.asarray(old, np.float64))
            .ravel())), jax.device_get(params), params0)
    return {"losses": losses, "grad1": g1, "grad1_raw": raw1,
            "change": change}
