"""Pallas kernels vs pure-jnp oracles: shape/dtype/mantissa sweeps, both
rounding modes, exact equality (shared quantize_block + xorshift stream)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.bfp_quantize import bfp_quantize_pallas
from repro.kernels.hbfp_matmul import (hbfp_dgrad_pallas, hbfp_matmul_pallas,
                                       hbfp_wgrad_pallas)

SHAPES_Q = [(64, 64), (128, 256), (192, 64), (256, 384), (100, 200),
            (130, 72)]
TILES = [(32, 32), (64, 64), (64, 128)]


@pytest.mark.parametrize("shape", SHAPES_Q)
@pytest.mark.parametrize("tile", TILES)
@pytest.mark.parametrize("m", [4, 8, 12])
def test_quantize_kernel_vs_ref(shape, tile, m):
    # non-divisible shapes pad-and-slice inside the wrapper (no skips)
    x = jax.random.normal(jax.random.key(hash((shape, tile, m)) % 2**31),
                          shape).astype(jnp.float32) * 3.3
    seed = jnp.zeros((1, 1), jnp.int32)
    mk, ek = bfp_quantize_pallas(x, seed, mantissa_bits=m, tile_r=tile[0],
                                 tile_c=tile[1], interpret=True)
    mr, er = ref.bfp_quantize_ref(x, 0, mantissa_bits=m, tile_r=tile[0],
                                  tile_c=tile[1])
    assert mk.shape == shape
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(mr))
    np.testing.assert_array_equal(np.asarray(ek), np.asarray(er))


@pytest.mark.parametrize("shape", [(128, 256), (100, 200)])
@pytest.mark.parametrize("m", [4, 8])
def test_quantize_kernel_fused_stats(shape, m):
    """Fused stat outputs (clip count per tile, exponent min/max per block)
    match the oracle and the pure-jnp observatory stats (DESIGN.md §9)."""
    x = jax.random.normal(jax.random.key(shape[0] + m), shape) * 2.1
    seed = jnp.zeros((1, 1), jnp.int32)
    outs = bfp_quantize_pallas(x, seed, mantissa_bits=m, tile_r=64,
                               tile_c=64, with_stats=True, interpret=True)
    refs = ref.bfp_quantize_ref(x, 0, mantissa_bits=m, tile_r=64, tile_c=64,
                                with_stats=True)
    for a, b in zip(outs, refs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mant, exp, clip_count, emin, emax = outs
    # cross-check vs the jnp observatory path on the padded array
    from repro.numerics.stats import quantize_with_stats
    Rp = -(-shape[0] // 64) * 64
    Cp = -(-shape[1] // 64) * 64
    xp = jnp.pad(x, ((0, Rp - shape[0]), (0, Cp - shape[1])))
    _, s = quantize_with_stats(xp, m, (64, 64))
    assert int(clip_count.sum()) == int(round(float(s.clip_frac * s.n)))
    assert int(emax.max() - emin.min()) == int(float(s.exp_spread))


@pytest.mark.parametrize("shape", [(128, 256), (100, 130)])
def test_ops_bfp_quantize_wrapper(shape):
    """The public ops wrapper: (m, e) matches the oracle on divisible AND
    pad-and-slice shapes; with_stats=True appends the aggregate dict."""
    x = jax.random.normal(jax.random.key(shape[1]), shape) * 3.0
    mk, ek = ops.bfp_quantize(x, mantissa_bits=4, tile=64)
    mr, er = ref.bfp_quantize_ref(x, 0, mantissa_bits=4, tile_r=64,
                                  tile_c=64)
    assert mk.shape == shape
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(mr))
    np.testing.assert_array_equal(np.asarray(ek), np.asarray(er))
    m2, e2, stats = ops.bfp_quantize(x, mantissa_bits=4, tile=64,
                                     with_stats=True)
    np.testing.assert_array_equal(np.asarray(m2), np.asarray(mk))
    np.testing.assert_array_equal(np.asarray(e2), np.asarray(ek))
    assert int(stats["exp_spread"]) == int(ek.max() - ek.min())
    assert float(stats["clip_frac"]) == float(stats["clip_count"]) / x.size
    # aggregate clip count == the observatory's element clip on same tiling
    from repro.numerics.stats import quantize_with_stats
    Rp, Cp = -(-shape[0] // 64) * 64, -(-shape[1] // 64) * 64
    xp = jnp.pad(x, ((0, Rp - shape[0]), (0, Cp - shape[1])))
    _, s = quantize_with_stats(xp, 4, (64, 64))
    assert int(stats["clip_count"]) == int(round(float(s.clip_frac * s.n)))


@pytest.mark.slow
@pytest.mark.parametrize("m", [4, 8])
def test_quantize_kernel_stochastic(m):
    x = jax.random.normal(jax.random.key(0), (128, 128)) * 0.7
    seed = jnp.full((1, 1), 99, jnp.int32)
    mk, _ = bfp_quantize_pallas(x, seed, mantissa_bits=m, tile_r=64,
                                tile_c=64, stochastic=True, interpret=True)
    mr, _ = ref.bfp_quantize_ref(x, 99, mantissa_bits=m, tile_r=64,
                                 tile_c=64, stochastic=True)
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(mr))


MM_CASES = [
    # (M, K, N, bm, bk, bn)
    (64, 64, 64, 64, 64, 64),
    (128, 128, 128, 64, 64, 64),
    (128, 256, 64, 64, 128, 32),
    (256, 128, 128, 128, 64, 128),
]


@pytest.mark.slow
@pytest.mark.parametrize("case", MM_CASES)
@pytest.mark.parametrize("m", [8, 12])
@pytest.mark.parametrize("stochastic", [False, True])
def test_matmul_kernel_vs_ref(case, m, stochastic):
    M, K, N, bm, bk, bn = case
    kx, kw = jax.random.split(jax.random.key(hash((case, m)) % 2**31))
    x = jax.random.normal(kx, (M, K)).astype(jnp.float32)
    w = (jax.random.normal(kw, (K, N)) * 0.1).astype(jnp.float32)
    seed = jnp.full((1, 1), 5, jnp.int32) if stochastic else None
    y = hbfp_matmul_pallas(x, w, seed, mantissa_bits=m,
                           stochastic=stochastic, bm=bm, bk=bk, bn=bn,
                           interpret=True)
    yr = ref.hbfp_matmul_ref(x, w, 5 if stochastic else None,
                             mantissa_bits=m, stochastic=stochastic,
                             bm=bm, bk=bk, bn=bn)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_kernel_dtypes(dtype):
    x = jax.random.normal(jax.random.key(0), (64, 64)).astype(dtype)
    w = jax.random.normal(jax.random.key(1), (64, 64)).astype(dtype)
    y = hbfp_matmul_pallas(x, w, None, mantissa_bits=8, bm=64, bk=64,
                           bn=64, interpret=True)
    yr = ref.hbfp_matmul_ref(x, w, mantissa_bits=8, bm=64, bk=64, bn=64)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))


def test_matmul_kernel_accuracy_vs_fp32():
    """Kernel output within the BFP error envelope of the fp32 product."""
    x = jax.random.normal(jax.random.key(0), (128, 512))
    w = jax.random.normal(jax.random.key(1), (512, 128)) / np.sqrt(512)
    y = ops.hbfp_matmul(x, w, mantissa_bits=8)
    rel = float(jnp.abs(y - x @ w).max() / jnp.abs(x @ w).max())
    assert rel < 0.05, rel
    y12 = ops.hbfp_matmul(x, w, mantissa_bits=12)
    rel12 = float(jnp.abs(y12 - x @ w).max() / jnp.abs(x @ w).max())
    assert rel12 < rel


def test_ops_padding_path():
    """Non-block-divisible shapes route through padding and slice back."""
    x = jax.random.normal(jax.random.key(0), (100, 200))
    w = jax.random.normal(jax.random.key(1), (200, 60)) * 0.1
    y = ops.hbfp_matmul(x, w, mantissa_bits=8, bm=64, bk=64, bn=32)
    assert y.shape == (100, 60)
    xp = jnp.pad(x, ((0, 28), (0, 56)))
    wp = jnp.pad(w, ((0, 56), (0, 4)))
    yr = ref.hbfp_matmul_ref(xp, wp, mantissa_bits=8, bm=64, bk=64,
                             bn=32)[:100, :60]
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))


def test_ops_batched():
    x = jax.random.normal(jax.random.key(0), (3, 32, 64))
    w = jax.random.normal(jax.random.key(1), (64, 16))
    y = ops.hbfp_matmul(x, w, mantissa_bits=8, bm=32, bk=64, bn=16)
    assert y.shape == (3, 32, 16)


def test_int8_path_exactness():
    """m<=8 kernel contracts int8 mantissas in int32 — verify the integer
    accumulation against a float recomputation of the same mantissas."""
    x = jax.random.normal(jax.random.key(0), (64, 64)) * 100
    w = jax.random.normal(jax.random.key(1), (64, 64)) * 1e-3
    y8 = hbfp_matmul_pallas(x, w, None, mantissa_bits=8, bm=64, bk=64,
                            bn=64, interpret=True)
    from repro.core import bfp
    xq = bfp.quantize(x, 8, (1, None))
    wq = bfp.quantize(w, 8, (None, None))
    np.testing.assert_allclose(np.asarray(y8), np.asarray(xq @ wq),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# large tiles: the exponent group stays 128 whatever the tile (DESIGN.md §13)
# ---------------------------------------------------------------------------

BIG = (256, 512, 384)               # (M, K, N), tiles (256, 512, 384)
GEMM_KERNELS = {"fwd": (hbfp_matmul_pallas, ref.hbfp_matmul_ref),
                "dgrad": (hbfp_dgrad_pallas, ref.hbfp_dgrad_ref),
                "wgrad": (hbfp_wgrad_pallas, ref.hbfp_wgrad_ref)}


def _grouped(key, rows, cols):
    """Normal values whose scale jumps per 128 x 128 block, so one
    exponent per tile row or tile would round them differently."""
    k1, k2 = jax.random.split(jax.random.key(key))
    scale = jnp.exp2(jnp.round(3 * jax.random.normal(
        k2, (rows // 128, cols // 128))))
    scale = jnp.repeat(jnp.repeat(scale, 128, 0), 128, 1)
    return jax.random.normal(k1, (rows, cols)) * scale


def _operands(op):
    M, K, N = BIG
    a_shape, b_shape = {"fwd": ((M, K), (K, N)), "dgrad": ((M, N), (K, N)),
                        "wgrad": ((M, K), (M, N))}[op]
    return _grouped(1, *a_shape), _grouped(2, *b_shape) * 0.1


@pytest.mark.parametrize("op", ["fwd", "dgrad"])
@pytest.mark.parametrize("stochastic", [False, True])
def test_large_tiles_bit_identical_to_128(op, stochastic):
    """fwd and dgrad at one large tile contract the same 128-groups in the
    same order as 128³ tiles: identical bits, and the oracle's at the
    large tile; a 512-wide exponent group (block=512) does differ."""
    kernel, oracle = GEMM_KERNELS[op]
    a, b = _operands(op)
    seed = jnp.full((1, 1), 7, jnp.int32) if stochastic else None
    kw = dict(mantissa_bits=8, stochastic=stochastic)
    big = kernel(a, b, seed, bm=256, bk=512, bn=384, interpret=True, **kw)
    small = kernel(a, b, seed, bm=128, bk=128, bn=128, interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(big), np.asarray(small))
    r = oracle(a, b, 7 if stochastic else None, bm=256, bk=512, bn=384, **kw)
    np.testing.assert_array_equal(np.asarray(big), np.asarray(r))
    coarse = kernel(a, b, seed, bm=256, bk=512, bn=384, block=512,
                    interpret=True, **kw)
    assert not np.array_equal(np.asarray(big), np.asarray(coarse))


@pytest.mark.parametrize("stochastic", [False, True])
def test_large_tiles_wgrad_agrees_to_f32_rounding(stochastic):
    """wgrad keeps its exponent groups at large tiles; only the f32 sum
    over a longer token tile may round differently."""
    kernel, oracle = GEMM_KERNELS["wgrad"]
    x, g = _operands("wgrad")
    seed = jnp.full((1, 1), 7, jnp.int32) if stochastic else None
    kw = dict(mantissa_bits=8, stochastic=stochastic)
    big = np.asarray(kernel(x, g, seed, bm=256, bk=512, bn=384,
                            interpret=True, **kw))
    small = np.asarray(kernel(x, g, seed, bm=128, bk=128, bn=128,
                              interpret=True, **kw))
    np.testing.assert_allclose(big, small, rtol=1e-5,
                               atol=1e-6 * np.abs(small).max())
    r = oracle(x, g, 7 if stochastic else None, bm=256, bk=512, bn=384, **kw)
    np.testing.assert_array_equal(big, np.asarray(r))


@pytest.mark.parametrize("op", ["fwd", "dgrad", "wgrad"])
def test_block16_large_tiles_match_oracle(op):
    """Sub-128 groups keep the dequantize-in-VMEM path at any tile."""
    kernel, oracle = GEMM_KERNELS[op]
    a, b = _operands(op)
    kw = dict(mantissa_bits=8, block=16, bm=256, bk=256, bn=128)
    np.testing.assert_array_equal(
        np.asarray(kernel(a, b, None, interpret=True, **kw)),
        np.asarray(oracle(a, b, None, **kw)))


@pytest.mark.slow
@pytest.mark.parametrize("m", [8, 12])
@pytest.mark.parametrize("shape", [(2, 64, 32), (1, 128, 64), (4, 32, 16)])
def test_flash_attention_vs_ref(m, shape):
    """Fused HBFP flash attention vs oracle (1-ulp tolerance: FMA order)."""
    from repro.kernels.hbfp_flash_attn import hbfp_flash_attention
    from repro.kernels.ref import hbfp_flash_attn_ref
    BH, S, hd = shape
    ks = jax.random.split(jax.random.key(m + S), 3)
    q, k, v = (jax.random.normal(kk, shape) for kk in ks)
    y = hbfp_flash_attention(q, k, v, m_bits=m, bq=32, bk=32,
                             interpret=True)
    yr = hbfp_flash_attn_ref(q, k, v, m_bits=m, bq=32, bk=32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-6)


@pytest.mark.slow
def test_flash_attention_matches_naive_fp32_envelope():
    from repro.kernels.hbfp_flash_attn import hbfp_flash_attention
    q = jax.random.normal(jax.random.key(0), (2, 64, 32))
    k = jax.random.normal(jax.random.key(1), (2, 64, 32))
    v = jax.random.normal(jax.random.key(2), (2, 64, 32))
    y8 = hbfp_flash_attention(q, k, v, m_bits=8, bq=32, bk=32,
                              interpret=True)
    s = (q @ jnp.swapaxes(k, -1, -2)) / np.sqrt(32)
    s = jnp.where(jnp.tril(jnp.ones((64, 64), bool)), s, -1e30)
    ref = jax.nn.softmax(s, -1) @ v
    rel8 = float(jnp.abs(y8 - ref).max() / jnp.abs(ref).max())
    assert rel8 < 0.05, rel8
    y12 = hbfp_flash_attention(q, k, v, m_bits=12, bq=32, bk=32,
                               interpret=True)
    rel12 = float(jnp.abs(y12 - ref).max() / jnp.abs(ref).max())
    assert rel12 < rel8  # accuracy improves with mantissa width


@pytest.mark.slow
def test_flash_attention_non_causal():
    from repro.kernels.hbfp_flash_attn import hbfp_flash_attention
    from repro.kernels.ref import hbfp_flash_attn_ref
    q = jax.random.normal(jax.random.key(5), (1, 64, 32))
    k = jax.random.normal(jax.random.key(6), (1, 64, 32))
    v = jax.random.normal(jax.random.key(7), (1, 64, 32))
    y = hbfp_flash_attention(q, k, v, causal=False, bq=32, bk=32,
                             interpret=True)
    yr = hbfp_flash_attn_ref(q, k, v, causal=False, bq=32, bk=32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-6)
