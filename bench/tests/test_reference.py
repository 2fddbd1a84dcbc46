"""The benchmark's f32 reference against the program's fp32 forward, at a
small size on the CPU."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
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
