"""Kernel training path (DESIGN.md §10): dgrad/wgrad Pallas kernels vs the
pure-jnp oracles (exact, both rounding modes, pad-and-slice shapes), the
custom-VJP matmul vs ref-composed and sim-autodiff gradients, the flash
attention custom VJP, the tile autotuner, and the train-step regression
proving kernel_backend="sim" (the flag off) is bit-identical to the
pre-existing path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core import HBFPConfig
from repro.core.hbfp_ops import hbfp_matmul as sim_matmul
from repro.kernels import autotune, ops, ref
from repro.kernels.hbfp_matmul import hbfp_dgrad_pallas, hbfp_wgrad_pallas
from repro.kernels.hbfp_matmul import vmem_bytes
from repro.kernels.linear import (gemm_events, hbfp_matmul_kernel,
                                  resolve_spec, seed_from_key)
from repro.models.layers import Ctx, ctx_matmul
from repro.obs import MemorySink, Recorder

BWD_CASES = [
    # (M, K, N, bm, bk, bn)
    (64, 64, 64, 64, 64, 64),
    (128, 256, 64, 64, 128, 32),
    (128, 128, 192, 64, 32, 64),
]


# ----------------------------------------------------------------------------
# backward kernels vs oracles (exact)
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("case", BWD_CASES)
@pytest.mark.parametrize("m", [8, 12])
def test_dgrad_kernel_vs_ref(case, m):
    M, K, N, bm, bk, bn = case
    g = jax.random.normal(jax.random.key(m), (M, N))
    w = jax.random.normal(jax.random.key(m + 1), (K, N)) * 0.1
    dx = hbfp_dgrad_pallas(g, w, mantissa_bits=m, bm=bm, bk=bk, bn=bn,
                           interpret=True)
    dxr = ref.hbfp_dgrad_ref(g, w, mantissa_bits=m, bm=bm, bk=bk, bn=bn)
    np.testing.assert_array_equal(np.asarray(dx), np.asarray(dxr))


@pytest.mark.parametrize("case", BWD_CASES)
@pytest.mark.parametrize("m", [8, 12])
def test_wgrad_kernel_vs_ref(case, m):
    M, K, N, bm, bk, bn = case
    x = jax.random.normal(jax.random.key(m), (M, K))
    g = jax.random.normal(jax.random.key(m + 2), (M, N))
    dw = hbfp_wgrad_pallas(x, g, mantissa_bits=m, bm=bm, bk=bk, bn=bn,
                           interpret=True)
    dwr = ref.hbfp_wgrad_ref(x, g, mantissa_bits=m, bm=bm, bk=bk, bn=bn)
    np.testing.assert_array_equal(np.asarray(dw), np.asarray(dwr))


@pytest.mark.slow
@pytest.mark.parametrize("case", BWD_CASES[1:])
@pytest.mark.parametrize("m", [4, 8])
def test_backward_kernels_stochastic_vs_ref(case, m):
    """Stochastic rounding: the in-kernel xorshift streams (STREAM_G/W/X
    offsets) replay exactly in the oracles."""
    M, K, N, bm, bk, bn = case
    x = jax.random.normal(jax.random.key(0), (M, K))
    g = jax.random.normal(jax.random.key(1), (M, N))
    w = jax.random.normal(jax.random.key(2), (K, N)) * 0.1
    seed = jnp.full((1, 1), 42, jnp.int32)
    dx = hbfp_dgrad_pallas(g, w, seed, mantissa_bits=m, stochastic=True,
                           bm=bm, bk=bk, bn=bn, interpret=True)
    dxr = ref.hbfp_dgrad_ref(g, w, 42, mantissa_bits=m, stochastic=True,
                             bm=bm, bk=bk, bn=bn)
    np.testing.assert_array_equal(np.asarray(dx), np.asarray(dxr))
    dw = hbfp_wgrad_pallas(x, g, seed, mantissa_bits=m, stochastic=True,
                           bm=bm, bk=bk, bn=bn, interpret=True)
    dwr = ref.hbfp_wgrad_ref(x, g, 42, mantissa_bits=m, stochastic=True,
                             bm=bm, bk=bk, bn=bn)
    np.testing.assert_array_equal(np.asarray(dw), np.asarray(dwr))


def test_quantize_w_false_uses_raw_weights():
    """quantize_w=False (pre-narrowed weights, per-layer widths): the fwd
    and dgrad kernels use w verbatim — re-quantizing at a narrower global
    width would crush schedule/controller overrides."""
    x = jax.random.normal(jax.random.key(0), (64, 64))
    g = jax.random.normal(jax.random.key(1), (64, 64))
    w = jax.random.normal(jax.random.key(2), (64, 64)) * 0.1
    from repro.kernels.hbfp_matmul import hbfp_matmul_pallas
    y = hbfp_matmul_pallas(x, w, mantissa_bits=8, quantize_w=False,
                           bm=64, bk=64, bn=64, interpret=True)
    yr = ref.hbfp_matmul_ref(x, w, mantissa_bits=8, quantize_w=False,
                             bm=64, bk=64, bn=64)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))
    dx = hbfp_dgrad_pallas(g, w, mantissa_bits=8, quantize_w=False,
                           bm=64, bk=64, bn=64, interpret=True)
    dxr = ref.hbfp_dgrad_ref(g, w, mantissa_bits=8, quantize_w=False,
                             bm=64, bk=64, bn=64)
    np.testing.assert_array_equal(np.asarray(dx), np.asarray(dxr))


def test_ops_dgrad_wgrad_padding_path():
    """Non-divisible shapes pad to the tile grid and slice back, matching
    the oracle on the explicitly padded problem."""
    g = jax.random.normal(jax.random.key(0), (100, 60))
    w = jax.random.normal(jax.random.key(1), (72, 60)) * 0.1
    x = jax.random.normal(jax.random.key(2), (100, 72))
    dx = ops.hbfp_dgrad(g, w, mantissa_bits=8, bm=64, bk=64, bn=32)
    gp = jnp.pad(g, ((0, 28), (0, 4)))
    wp = jnp.pad(w, ((0, 56), (0, 4)))
    dxr = ref.hbfp_dgrad_ref(gp, wp, mantissa_bits=8, bm=64, bk=64,
                             bn=32)[:100, :72]
    np.testing.assert_array_equal(np.asarray(dx), np.asarray(dxr))
    dw = ops.hbfp_wgrad(x, g, mantissa_bits=8, bm=64, bk=64, bn=32)
    xp = jnp.pad(x, ((0, 28), (0, 56)))
    gp2 = jnp.pad(g, ((0, 28), (0, 4)))
    dwr = ref.hbfp_wgrad_ref(xp, gp2, mantissa_bits=8, bm=64, bk=64,
                             bn=32)[:72, :60]
    np.testing.assert_array_equal(np.asarray(dw), np.asarray(dwr))


# ----------------------------------------------------------------------------
# custom VJP (the training op)
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("rounding", ["nearest", "stochastic"])
def test_custom_vjp_grads_match_ref_oracles(rounding):
    """jax.grad through hbfp_matmul_kernel == the ref dgrad/wgrad oracles
    composed per the VJP dataflow — exactly, on a non-divisible shape that
    exercises the pad-and-slice path in fwd AND bwd (tiles clip to the
    dims, so only M > 128 actually pads — K and N keep their strides,
    which the stochastic streams depend on)."""
    cfg = HBFPConfig(8, 16, rounding=rounding)
    key = jax.random.key(11)
    M, K, N = 150, 72, 60  # M pads 150 -> 256, as with a 128 tile
    spec = resolve_spec(cfg, M, K, N)
    x = jax.random.normal(jax.random.key(0), (M, K))
    w = jax.random.normal(jax.random.key(1), (K, N)) * 0.1

    def loss(x, w):
        return (hbfp_matmul_kernel(x, w, cfg, key) ** 2).sum()

    dx, dw = jax.grad(loss, argnums=(0, 1))(x, w)
    y = hbfp_matmul_kernel(x, w, cfg, key)
    g = 2 * y
    seed = int(seed_from_key(key)[0, 0]) if rounding == "stochastic" \
        else None
    st = rounding == "stochastic"
    gp = jnp.pad(g, ((0, 256 - M), (0, 0)))
    xp = jnp.pad(x, ((0, 256 - M), (0, 0)))
    bm, bk, bn = spec.dgrad
    dxr = ref.hbfp_dgrad_ref(gp, w, seed, mantissa_bits=8, stochastic=st,
                             bm=bm, bk=bk, bn=bn)[:M, :K]
    bm, bk, bn = spec.wgrad  # the token tile sets wgrad's f32 sum order
    dwr = ref.hbfp_wgrad_ref(xp, gp, seed, mantissa_bits=8, stochastic=st,
                             bm=bm, bk=bk, bn=bn)[:K, :N]
    np.testing.assert_array_equal(np.asarray(dx), np.asarray(dxr))
    np.testing.assert_array_equal(np.asarray(dw), np.asarray(dwr))


def test_custom_vjp_matches_sim_autodiff():
    """With aligned exponent groupings (act_block == bk == bn == tile) the
    kernel path's gradients coincide with autodiff through the simulation
    custom VJP (hbfp_ops) — the two implementations of the same §4.1
    semantics agree."""
    cfg_k = HBFPConfig(8, 16)
    cfg_s = HBFPConfig(8, 16, tile=128, act_block=128)
    x = jax.random.normal(jax.random.key(0), (100, 72))
    w = jax.random.normal(jax.random.key(1), (72, 60)) * 0.1
    dxk, dwk = jax.grad(
        lambda x, w: (hbfp_matmul_kernel(x, w, cfg_k) ** 2).sum(),
        argnums=(0, 1))(x, w)
    dxs, dws = jax.grad(
        lambda x, w: (sim_matmul(x, w, cfg_s) ** 2).sum(),
        argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(dxk), np.asarray(dxs), atol=1e-5)
    np.testing.assert_allclose(np.asarray(dwk), np.asarray(dws), atol=1e-5)


def test_custom_vjp_int8_path_exact_vs_dequant():
    """m ≤ 8 dgrad rides the int8 MXU path; its int32 accumulation must
    equal the f32 recomputation of the same mantissas (the acceptance
    criterion's 'exact where mantissa ≤ 8')."""
    from repro.core import bfp
    g = jax.random.normal(jax.random.key(0), (64, 64)) * 100
    w = jax.random.normal(jax.random.key(1), (64, 64)) * 1e-3
    dx = hbfp_dgrad_pallas(g, w, mantissa_bits=8, bm=64, bk=64, bn=64,
                           interpret=True)
    gq = bfp.quantize(g, 8, (1, None))
    wq = bfp.quantize(w, 8, (None, None))
    np.testing.assert_allclose(np.asarray(dx), np.asarray(gq @ wq.T),
                               rtol=1e-6)


def test_custom_vjp_batched_leading_dims():
    """[B, S, K] inputs flatten into the kernel's M and reshape back; the
    VJP returns dx in the original batched shape."""
    cfg = HBFPConfig(8, 16)
    x = jax.random.normal(jax.random.key(0), (3, 32, 64))
    w = jax.random.normal(jax.random.key(1), (64, 16)) * 0.1
    y, vjp = jax.vjp(lambda x, w: hbfp_matmul_kernel(x, w, cfg), x, w)
    assert y.shape == (3, 32, 16)
    dx, dw = vjp(jnp.ones_like(y))
    assert dx.shape == x.shape and dw.shape == w.shape
    assert bool(jnp.isfinite(dx).all() and jnp.isfinite(dw).all())


# ----------------------------------------------------------------------------
# flash attention custom VJP
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("m", [8, 12])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_bwd_kernels_vs_ref(m, causal):
    from repro.kernels.hbfp_flash_attn import (hbfp_flash_attention,
                                               hbfp_flash_attention_bwd)
    BH, S, hd = 2, 64, 32
    ks = jax.random.split(jax.random.key(m + causal), 4)
    q, k, v, do = (jax.random.normal(kk, (BH, S, hd)) for kk in ks)
    o, lse = hbfp_flash_attention(q, k, v, m_bits=m, bq=32, bk=32,
                                  causal=causal, with_lse=True,
                                  interpret=True)
    orf, lser = ref.hbfp_flash_attn_ref(q, k, v, m_bits=m, bq=32, bk=32,
                                        causal=causal, with_lse=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf), atol=1e-6)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lser), atol=1e-6)
    dq, dk, dv = hbfp_flash_attention_bwd(q, k, v, o, lse, do, m_bits=m,
                                          bq=32, bk=32, causal=causal,
                                          interpret=True)
    dqr, dkr, dvr = ref.hbfp_flash_attn_vjp_ref(q, k, v, do, m_bits=m,
                                                bq=32, bk=32, causal=causal)
    # 1-ulp tolerance (FMA/order), same as the forward oracle tests
    np.testing.assert_allclose(np.asarray(dq), np.asarray(dqr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dkr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(dvr), atol=1e-6)


@pytest.mark.slow
def test_flash_vjp_grads_track_fp32_attention():
    from repro.kernels.hbfp_flash_attn import FlashSpec, flash_attention_vjp
    BH, S, hd = 2, 64, 32
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (BH, S, hd)) for kk in ks)
    spec = FlashSpec(8, 32, 32, True, True)

    def loss_flash(q, k, v):
        return (flash_attention_vjp(spec, q, k, v) ** 2).sum()

    def loss_fp32(q, k, v):
        s = (q @ jnp.swapaxes(k, -1, -2)) / np.sqrt(hd)
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
        return ((jax.nn.softmax(s, -1) @ v) ** 2).sum()

    g8 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g32 = jax.grad(loss_fp32, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g8, g32):
        rel = float(jnp.abs(a - b).max() / jnp.abs(b).max())
        assert rel < 0.08, rel


# ----------------------------------------------------------------------------
# autotuner
# ----------------------------------------------------------------------------

def test_autotune_candidates_clip_dedupe_and_budget():
    c = autotune.candidates(64, 64, 64)
    assert len(c) == len(set(c))
    assert all(t[0] <= 64 and t[1] <= 64 and t[2] <= 64 for t in c)
    # a tiny budget filters everything but the smallest tiles
    small = autotune.candidates(512, 512, 512, budget=50 * 1024)
    assert small and all(autotune.vmem_bytes(*t) <= 50 * 1024 for t in small)
    assert (512, 512, 512) not in small


def test_autotune_table_roundtrip_and_lookup(tmp_path, monkeypatch):
    path = str(tmp_path / "tune.json")
    monkeypatch.setenv(autotune.TABLE_ENV, path)
    autotune.invalidate_cache()
    # untuned ⇒ the shape rule: whole dims up to the rule's edges
    assert autotune.lookup("matmul_fwd", 64, 256, 512) == (64, 256, 512)
    assert autotune.shape_tiles("matmul_fwd", 64, 256, 512) == (64, 256, 512)
    t = autotune.TuningTable.load()
    key = autotune.cache_key("matmul_fwd", 64, 256, 512, "float32", 8)
    t.put(key, (32, 64, 256), us=1.0, speedup=2.0)
    t.save()
    autotune.invalidate_cache()
    assert autotune.lookup("matmul_fwd", 64, 256, 512) == (32, 64, 256)
    # different mantissa width is a different cell ⇒ default again
    assert autotune.lookup("matmul_fwd", 64, 256, 512,
                           mantissa_bits=12) == (64, 256, 512)
    autotune.invalidate_cache()


def test_autotune_op_records_winner(tmp_path, monkeypatch):
    monkeypatch.setenv(autotune.TABLE_ENV, str(tmp_path / "t.json"))
    autotune.invalidate_cache()
    table = autotune.TuningTable(path=str(tmp_path / "t.json"))
    x = jax.random.normal(jax.random.key(0), (64, 64))
    w = jax.random.normal(jax.random.key(1), (64, 64)) * 0.1
    best, rep = autotune.autotune_op(
        "matmul_fwd", lambda t: ops.hbfp_matmul(
            x, w, mantissa_bits=8, bm=t[0], bk=t[1], bn=t[2]),
        64, 64, 64, table=table, menu=(32, 64), n=1)
    assert rep["speedup"] >= 1.0  # the winner is at least the default
    assert tuple(rep["tiles"]) == best
    # ops.py now resolves this shape to the tuned tiles
    assert autotune.lookup("matmul_fwd", 64, 64, 64) == best
    autotune.invalidate_cache()


def test_ops_resolves_tiles_from_table(tmp_path, monkeypatch):
    """ops.hbfp_matmul with unspecified tiles consults the table at trace
    time; a tuned entry changes the blocking but not the math."""
    monkeypatch.setenv(autotune.TABLE_ENV, str(tmp_path / "t.json"))
    autotune.invalidate_cache()
    x = jax.random.normal(jax.random.key(0), (128, 128))
    w = jax.random.normal(jax.random.key(1), (128, 128)) * 0.1
    y_default = ops.hbfp_matmul(x, w, mantissa_bits=8)
    t = autotune.TuningTable.load()
    t.put(autotune.cache_key("matmul_fwd", 128, 128, 128, "float32", 8),
          (64, 64, 64))
    t.save()
    autotune.invalidate_cache()
    y_tuned = ops.hbfp_matmul(x, w, mantissa_bits=8)
    y_explicit = ops.hbfp_matmul(x, w, mantissa_bits=8, bm=64, bk=64, bn=64)
    np.testing.assert_array_equal(np.asarray(y_tuned),
                                  np.asarray(y_explicit))
    # same quantization groups here (per-row × whole-tile unaffected by the
    # K split? no — bk differs ⇒ values may differ from default blocking):
    # only assert both are close to fp32 at the 8-bit envelope
    rel = float(jnp.abs(y_tuned - x @ w).max() / jnp.abs(x @ w).max())
    assert rel < 0.05
    del y_default
    autotune.invalidate_cache()


# ----------------------------------------------------------------------------
# train-step regression: flag off ⇒ today's path, flag on ⇒ kernels
# ----------------------------------------------------------------------------

def _tiny_arch(**kw):
    return ArchConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                      head_dim=16, loss_chunk=0, **kw)


def _batch(B=2, S=32, V=256):
    return {"tokens": jax.random.randint(jax.random.key(1), (B, S), 0, V),
            "labels": jax.random.randint(jax.random.key(2), (B, S), 0, V)}


def test_ctx_matmul_sim_backend_is_todays_path():
    """backend="sim" dispatch == a direct hbfp_ops.hbfp_matmul call,
    bit-for-bit, for weight-kind, act-kind, and batched operands."""
    cfg = HBFPConfig(8, 16)
    ctx = Ctx(cfg)  # default backend "sim"
    x = jax.random.normal(jax.random.key(0), (4, 16, 64))
    w = jax.random.normal(jax.random.key(1), (64, 32)) * 0.1
    np.testing.assert_array_equal(
        np.asarray(ctx_matmul(x, w, ctx, "s")),
        np.asarray(sim_matmul(x, w, cfg, None)))
    kt = jax.random.normal(jax.random.key(2), (4, 64, 16))
    np.testing.assert_array_equal(
        np.asarray(ctx_matmul(x, kt, ctx, "s", w_kind="act")),
        np.asarray(sim_matmul(x, kt, cfg, None, w_kind="act")))


def test_train_step_flag_off_bit_identical(monkeypatch):
    """The flag-off (default "sim") train step is bit-identical to TODAY'S
    path: every module's ctx_matmul binding is monkeypatched to call
    hbfp_ops.hbfp_matmul directly (the pre-dispatcher composition), a
    reference run is taken, and the unpatched default step must reproduce
    its loss and params exactly."""
    from repro.models import (attention, init_params, layers, moe, ssm,
                              transformer, xlstm)
    from repro.optim import make_schedule
    from repro.train import init_train_state, make_train_step
    arch = _tiny_arch()
    assert arch.kernel_backend == "sim"
    sched = make_schedule("constant", base_lr=1e-3, warmup_steps=0,
                          total_steps=10)
    batch = _batch()

    def run():
        step = jax.jit(make_train_step(arch, HBFPConfig(8, 16), sched))
        state = init_train_state(jax.random.key(0), arch, init_params)
        for i in range(2):
            state, m = step(state, batch, jax.random.key(i))
        return state, m

    def legacy(x, w, ctx, site, cfg=layers._UNSET, w_kind="weight"):
        cfg = ctx.cfg if cfg is layers._UNSET else cfg
        return sim_matmul(x, w, cfg, ctx.key_for(site), w_kind=w_kind)

    with monkeypatch.context() as mp:
        for mod in (layers, attention, transformer, moe, ssm, xlstm):
            mp.setattr(mod, "ctx_matmul", legacy)
        s_ref, m_ref = run()
    s_new, m_new = run()
    assert float(m_ref["loss"]) == float(m_new["loss"])
    for a, b in zip(jax.tree.leaves(s_ref.params),
                    jax.tree.leaves(s_new.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_train_step_pallas_backend_learns_and_tracks_sim():
    """kernel_backend="pallas": the whole train step's dot products run on
    the fused kernels (interpret mode on CPU) — loss is finite, decreases
    on a repeated batch, and tracks the sim backend closely."""
    from repro.models import init_params
    from repro.optim import make_schedule
    from repro.train import init_train_state, make_train_step
    arch_p = _tiny_arch(kernel_backend="pallas")
    arch_s = _tiny_arch()
    sched = make_schedule("constant", base_lr=1e-3, warmup_steps=0,
                          total_steps=10)
    batch = _batch()
    state0 = init_train_state(jax.random.key(0), arch_p, init_params)
    step_p = jax.jit(make_train_step(arch_p, HBFPConfig(8, 16), sched))
    s, m1 = step_p(state0, batch, jax.random.key(3))
    s, m2 = step_p(s, batch, jax.random.key(4))
    assert np.isfinite(float(m1["loss"])) and np.isfinite(float(m2["loss"]))
    assert float(m2["loss"]) < float(m1["loss"])
    step_s = jax.jit(make_train_step(arch_s, HBFPConfig(8, 16), sched))
    _, ms = step_s(state0, batch, jax.random.key(3))
    rel = abs(float(m1["loss"]) - float(ms["loss"])) / float(ms["loss"])
    assert rel < 0.02, rel


def test_flash_gate_accepts_concrete_arange_positions(monkeypatch):
    """The flash kernel masks by block index — valid whenever positions
    ARE the standard contiguous arange, whether synthesized or spelled out
    explicitly in the batch (the gate inspects concrete position values on
    the host). Packed/offset layouts and traced positions (uninspectable
    at trace time) keep the value-masking mha fallback."""
    from repro.models import attention, transformer
    from repro.models import init_params as _ip
    arch = _tiny_arch(kernel_backend="pallas")
    params = _ip(jax.random.key(0), arch)
    ctx = Ctx(HBFPConfig(8, 16), backend="pallas")
    calls = []
    real = attention.flash_mha
    monkeypatch.setattr(
        attention, "flash_mha",
        lambda *a, **k: (calls.append(1), real(*a, **k))[1])
    tok = jax.random.randint(jax.random.key(1), (2, 32), 0, 256)
    out_syn, _ = transformer.forward(params, {"tokens": tok}, arch, ctx)
    assert calls, "synthesized positions should take the flash path"
    calls.clear()
    pos = jnp.broadcast_to(jnp.arange(32, dtype=jnp.int32)[None], (2, 32))
    out_exp, _ = transformer.forward(
        params, {"tokens": tok, "positions": pos}, arch, ctx)
    assert calls, "explicit-but-arange positions now take the flash path"
    # same fast path, same numbers: spelling out the default layout is a
    # bit-identical no-op
    np.testing.assert_array_equal(np.asarray(out_syn), np.asarray(out_exp))
    calls.clear()
    transformer.forward(params, {"tokens": tok, "positions": pos + 3},
                        arch, ctx)
    assert not calls, "offset positions must stay on the mha path"
    calls.clear()
    jax.jit(lambda p, b: transformer.forward(p, b, arch, ctx)[0])(
        params, {"tokens": tok, "positions": pos})
    assert not calls, "traced positions can't be inspected and stay gated"


@pytest.mark.parametrize("m_qk,m_pv", [(10, 0), (0, 6), (12, 6)])
def test_flash_per_role_widths_vs_ref(m_qk, m_pv):
    """Per-role QK/PV widths through the fused flash kernels match the
    oracle at the same widths and differ from the uniform-width result."""
    from repro.kernels.hbfp_flash_attn import (hbfp_flash_attention,
                                               hbfp_flash_attention_bwd)
    BH, S, hd = 2, 64, 32
    ks = jax.random.split(jax.random.key(m_qk * 31 + m_pv), 4)
    q, k, v, do = (jax.random.normal(kk, (BH, S, hd)) for kk in ks)
    o, lse = hbfp_flash_attention(q, k, v, m_bits=8, m_qk=m_qk, m_pv=m_pv,
                                  bq=32, bk=32, with_lse=True,
                                  interpret=True)
    orf, lser = ref.hbfp_flash_attn_ref(q, k, v, m_bits=8, m_qk=m_qk,
                                        m_pv=m_pv, bq=32, bk=32,
                                        with_lse=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf), atol=1e-6)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lser), atol=1e-6)
    uni = hbfp_flash_attention(q, k, v, m_bits=8, bq=32, bk=32,
                               interpret=True)
    assert not np.array_equal(np.asarray(o), np.asarray(uni))
    dq, dk, dv = hbfp_flash_attention_bwd(q, k, v, o, lse, do, m_bits=8,
                                          m_qk=m_qk, m_pv=m_pv, bq=32,
                                          bk=32, interpret=True)
    dqr, dkr, dvr = ref.hbfp_flash_attn_vjp_ref(q, k, v, do, m_bits=8,
                                                m_qk=m_qk, m_pv=m_pv,
                                                bq=32, bk=32)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(dqr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dkr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(dvr), atol=1e-6)


@pytest.mark.slow
def test_train_step_pallas_stochastic_rounding():
    from repro.models import init_params
    from repro.optim import make_schedule
    from repro.train import init_train_state, make_train_step
    arch = _tiny_arch(kernel_backend="pallas")
    sched = make_schedule("constant", base_lr=1e-3, warmup_steps=0,
                          total_steps=10)
    step = jax.jit(make_train_step(
        arch, HBFPConfig(8, 16, rounding="stochastic"), sched))
    state = init_train_state(jax.random.key(0), arch, init_params)
    _, m = step(state, _batch(), jax.random.key(3))
    assert np.isfinite(float(m["loss"]))


# weight GEMMs (M, K, N) of the benchmark's configurations at B·S = 4096
# tokens (the head on loss chunks of 2048): yi-9b-4L (q/o, k/v, FFN up
# and down, head) and minicpm-2b-10L
BENCH_GEMMS = {
    "yi.attn_qo": (4096, 4096, 4096), "yi.attn_kv": (4096, 4096, 512),
    "yi.ffn_up": (4096, 4096, 11008), "yi.ffn_down": (4096, 11008, 4096),
    "yi.head": (2048, 4096, 8000),
    "minicpm.attn": (4096, 2304, 2304), "minicpm.ffn_up": (4096, 2304, 5760),
    "minicpm.ffn_down": (4096, 5760, 2304),
    "minicpm.head": (2048, 2304, 15344),
}
# (rows, depth, cols) of each op's (bm, bk, bn) tiles
_TILE_ROLES = {"matmul_fwd": (0, 1, 2), "matmul_dgrad": (0, 2, 1),
               "matmul_wgrad": (1, 0, 2)}


def _gemm_events(cfg, M, K, N):
    sink = MemorySink()
    with gemm_events(Recorder([sink])):
        spec = resolve_spec(cfg, M, K, N)
    evs = {e.data["op"]: e.data for e in sink.events
           if e.kind == "kernel/gemm"}
    return spec, evs


@pytest.mark.parametrize("gemm", sorted(BENCH_GEMMS))
def test_shape_rule_tiles_and_paths(gemm):
    """Untuned tiles are multiples of 128 that divide the 128-padded dims
    (no padding beyond a 128 tile's) and fit the VMEM budget; the trace-
    time event reports them, fwd and dgrad on the int8 per-group path.
    block=16 keeps today's tiles on the f32 dequantize path."""
    M, K, N = BENCH_GEMMS[gemm]
    padded = [-(-d // 128) * 128 for d in (M, K, N)]
    spec, evs = _gemm_events(HBFPConfig(8, 16), M, K, N)
    for op, tiles in (("matmul_fwd", spec.fwd), ("matmul_dgrad", spec.dgrad),
                      ("matmul_wgrad", spec.wgrad)):
        assert all(t % 128 == 0 and p % t == 0
                   for t, p in zip(tiles, padded)), (op, tiles)
        assert vmem_bytes(*(tiles[a] for a in _TILE_ROLES[op])) \
            <= autotune.VMEM_BUDGET_BYTES
        assert tiles[_TILE_ROLES[op][1]] <= autotune.RULE_DEPTH
        assert evs[op]["tiles"] == list(tiles)
        assert evs[op]["shape"] == [M, K, N]
    assert evs["matmul_fwd"]["path"] == "int8_group"
    assert evs["matmul_dgrad"]["path"] == "int8_group"
    assert evs["matmul_wgrad"]["path"] == "f32_wgrad"
    spec16, evs16 = _gemm_events(HBFPConfig(8, 16).with_block(16), M, K, N)
    assert spec16.fwd == spec16.dgrad == spec16.wgrad == (128, 128, 128)
    assert evs16["matmul_fwd"]["path"] == "f32_group"
    assert evs16["matmul_dgrad"]["path"] == "f32_group"


def test_rule_tiles_pad_like_128_tiles():
    """A dim over 128 that is not a multiple of it pads to the next 128,
    as with 128 tiles — the rule's whole-dim edge is never clipped to a
    ragged one — and gives the 128 tiles' bits."""
    assert autotune.lookup("matmul_fwd", 64, 200, 500) == (64, 256, 512)
    x = jax.random.normal(jax.random.key(0), (64, 200))
    w = jax.random.normal(jax.random.key(1), (200, 500)) * 0.1
    np.testing.assert_array_equal(
        np.asarray(ops.hbfp_matmul(x, w, mantissa_bits=8)),
        np.asarray(ops.hbfp_matmul(x, w, mantissa_bits=8, bm=64, bk=128,
                                   bn=128)))


def test_gemm_events_reach_the_recorder_at_trace():
    """A make_step recorder with sinks gets one `kernel/gemm` event per
    GEMM of every kernel call site when the step traces (fwd and dgrad on
    the int8 path under a uniform policy); outside `gemm_events` nothing
    is sent."""
    from repro.models import init_params
    from repro.optim import make_schedule
    from repro.train import init_train_state, make_step
    arch = _tiny_arch(kernel_backend="pallas")
    sink = MemorySink()
    step = make_step(arch, "8; backend=pallas",
                     make_schedule("constant", base_lr=1e-3, warmup_steps=0,
                                   total_steps=10),
                     recorder=Recorder([sink]))
    state = jax.eval_shape(
        lambda k: init_train_state(k, arch, init_params), jax.random.key(0))
    jax.eval_shape(step, state, _batch(), jax.random.key(3))
    evs = [e.data for e in sink.events if e.kind == "kernel/gemm"]
    paths = {}
    for e in evs:
        paths.setdefault(e["op"], set()).add(e["path"])
    assert paths == {"matmul_fwd": {"int8_group"},
                     "matmul_dgrad": {"int8_group"},
                     "matmul_wgrad": {"f32_wgrad"}}
    assert len(evs) == 3 * 8     # q, k, v, o, three FFN, head
    n = len(sink.events)
    resolve_spec(HBFPConfig(8, 16), 96, 64, 32)
    assert len(sink.events) == n
