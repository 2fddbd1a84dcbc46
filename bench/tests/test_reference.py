"""The benchmark's f32 reference against the program's fp32 forward, at a
small size on the CPU, and the fp8 rounding a control puts in it."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import reference
import structures
import tiny
from program import program_arch
from weights import make_params, seed_key, train_batch


@pytest.mark.parametrize("cfg", [tiny.TINY_CFG, tiny.TINY_MINICPM],
                         ids=["yi", "minicpm"])
def test_reference_matches_the_program_in_fp32(cfg):
    from repro.models import Ctx, forward, loss_fn
    cfg = dict(cfg, torch_dtype="float32")
    arch = dataclasses.replace(program_arch(cfg), remat=False)
    params = make_params(cfg, seed_key(2**40 + 3), jnp.float32)
    batch = train_batch(seed_key(9), 0, 2, 48, cfg["vocab_size"])
    ctx = Ctx(compute_dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = forward(params, {"tokens": batch["tokens"]}, arch, ctx)[0]
        got_loss = loss_fn(params, batch, arch, ctx)[0]
    want = reference.logits(cfg, params, batch["tokens"])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    assert float(got_loss) == pytest.approx(
        float(reference.loss(cfg, params, batch)), abs=1e-4)


def test_reference_adamw_moves_every_leaf():
    cfg = tiny.TINY_CFG
    key = seed_key(4)
    params = jax.device_get(make_params(cfg, key))
    hp = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
          "weight_decay": 0.1, "grad_clip": 1.0}
    batches = [train_batch(key, s, 2, 32, cfg["vocab_size"])
               for s in range(3)]
    r = reference.train_readings(cfg, hp, params, batches)
    assert len(r["losses"]) == 3
    for leaf in jax.tree.leaves(r["change"]):
        assert leaf > 0
    # the first clipped gradient has global norm at most the clip
    total = sum(float(x) ** 2 for x in jax.tree.leaves(r["grad1"])) ** 0.5
    assert total <= 1.0 + 1e-5


def test_fp8_rounding_applies_inside_its_context_only():
    a = jax.random.normal(jax.random.key(0), (64, 128), jnp.float32)
    b = jax.random.normal(jax.random.key(1), (128, 32), jnp.float32)
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)

    def loss(a, b):
        return jnp.sum(structures.mm("mk,kn->mn", a, b) ** 2)

    with structures.rounded("fp8"):
        out = structures.mm("mk,kn->mn", a, b)
        val8, g8 = jax.value_and_grad(loss, argnums=(0, 1))(a, b)
    val, g = jax.value_and_grad(loss, argnums=(0, 1))(a, b)
    err = np.abs(np.asarray(out) - exact).max() / np.abs(exact).max()
    assert 1e-3 < err < 0.1        # e4m3 keeps three mantissa bits
    assert abs(float(val8) / float(val) - 1) < 0.1
    for x8, x in zip(g8, g):
        rel = float(jnp.linalg.norm(x8 - x) / jnp.linalg.norm(x))
        assert 1e-3 < rel < 0.2
    np.testing.assert_allclose(structures.mm("mk,kn->mn", a, b), exact,
                               rtol=1e-5, atol=1e-4)


def test_unknown_rounding_is_a_setup_error():
    with pytest.raises(harness.SetupError):
        with structures.rounded("int3"):
            pass
