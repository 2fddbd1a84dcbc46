"""Pallas TPU kernel: fused HBFP matmul — the paper's MatMul unit (§5.3).

    y[M,N] = sum_g  ( Q_row(x)[M,K_g] · Q_tile(w)[K_g,N] ) · δx·δw

TPU adaptation of the paper's FPGA dataflow:
  * the BFP exponent group is fixed by the format, not by the kernel tile
    (DESIGN.md §13): at block=0 activations get one exponent per row per
    128 contraction columns (the paper's "one exponent per training
    input", refined to the group so conversion fuses with the matmul) and
    weights one exponent per 128 x 128 sub-tile (the paper's square weight
    tiles, 128-aligned for the MXU instead of the FPGA's 24). A kernel tile
    holds whole groups and loops over them, so tiles are a speed choice;
  * mantissas are contracted on the MXU one exponent group at a time —
    int8 path for m ≤ 8 (2× bf16 throughput on v5e, the paper's
    "fixed-point logic"), exact-f32 path for 8 < m ≤ 12;
  * each group's partial product is rescaled by δx[bm,1]·δw[1,bn] (δw
    broadcast per 128 columns) and accumulated in an f32 VMEM scratch, in
    ascending group order across the tile and the K grid — the paper's
    "wide accumulators"/"tiles accumulated in floating point" (§4.2
    Tiling), so the MatMul unit never overflows or saturates, and any
    tiling of whole groups gives the same bits;
  * FP→BFP conversion happens in VMEM right before the MXU op (the paper's
    "convert to BFP right before dot products", §4), with in-kernel xorshift
    stochastic rounding keyed by global element index.

Grid: (M/bm, N/bn, K/bk), K innermost ("arbitrary") so the accumulator
carries across K steps; M/N dims are parallel. Exponent groups under 128
(`block` < 128) keep the dequantize-in-VMEM path: per 128-column slice the
mantissas are rescaled in VMEM and contracted on the f32 MXU.

Backward GEMMs (docs/KERNELS.md, DESIGN.md §10): the paper's claim is that
*all three* training GEMMs run in BFP, so dgrad and wgrad are fused Pallas
kernels too, not autodiff through the forward:

  dgrad:  dx[M,K] = Σ_g Q_row(dy)[M,N_g] · Q_tile(w)[K,N_g]^T · δg·δw
  wgrad:  dw[K,N] = Σ_m  x̂[m,K] ⊗ ĝ[m,N]                   (FP accumulate)

dgrad mirrors the forward (activation rows × weight tiles, int8 MXU path,
w read transposed via the contraction dimension-numbers — no HBM transpose).
wgrad contracts over the token axis, where the paper's per-training-input
exponents live: the per-token scales δx[m]·δg[m] cannot factor out of the
dot, so mantissas are rescaled in VMEM (exact in f32 for m ≤ 12) and the
outer products accumulate in the f32 scratch — the paper's "weight updates
are computed as FP accumulations of BFP outer products" (§4.1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (GROUP, STREAM_G, STREAM_W, STREAM_X,
                                  bfp_step, check_slices, dequantize_rows,
                                  group_maxima, quantize_block,
                                  row_group_amax, slice_width, small_groups,
                                  spread, tile_group_amax)

# Scoped VMEM a kernel asks for: its `vmem_bytes` with a quarter and 4 MiB
# of room (Mosaic's own need was 0.7-1.2x the estimate at the benchmark's
# tiles), no less than Mosaic's 16 MiB default and well inside a v5e
# core's 128 MiB; no more, since XLA cannot use what a kernel reserves.
VMEM_FLOOR_BYTES = 16 * 2 ** 20
VMEM_CEIL_BYTES = 100 * 2 ** 20


def vmem_bytes(rows: int, depth: int, cols: int, itemsize: int = 4) -> int:
    """VMEM of one GEMM kernel tile: double-buffered operand and output
    blocks, the f32 accumulator and the body's temporaries (a group's
    partial product, its scale and the running sum: three more [rows,
    cols] f32 arrays)."""
    operands = (rows * depth + depth * cols + rows * cols) * itemsize * 2
    return operands + 4 * rows * cols * 4


def gemm_path(op: str, *, mantissa_bits: int, quantize_w: bool,
              block: int, tiles) -> str:
    """Which contraction a kernel runs, from what it observes:
    "int8_group" (int8 MXU per exponent group, rescaled in the f32
    accumulator), "f32_group" (f32 MXU per group: 12/16-bit mantissas,
    pre-narrowed weights, or sub-128 groups dequantized in VMEM) or
    "f32_wgrad" (dequantized f32 outer products over tokens). `op` is
    "matmul_fwd", "matmul_dgrad" or "matmul_wgrad"; `tiles` its (bm, bk,
    bn) as the kernel's wrapper takes them."""
    if op == "matmul_wgrad":
        return "f32_wgrad"
    _, bk, bn = tiles
    depth, other = (bn, bk) if op == "matmul_dgrad" else (bk, bn)
    s = slice_width(block, depth)
    if (not quantize_w or mantissa_bits > 8
            or small_groups(block, s, other)):
        return "f32_group"
    return "int8_group"


def _params(rows: int, depth: int, cols: int, itemsize: int):
    need = vmem_bytes(rows, depth, cols, itemsize) * 5 // 4 + 4 * 2 ** 20
    return pltpu.CompilerParams(vmem_limit_bytes=min(
        max(need, VMEM_FLOOR_BYTES), VMEM_CEIL_BYTES))


def _iota2(shape):
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _int_dot(a, b, dims):
    """int8 mantissas on the MXU, exact int32 accumulate (m ≤ 8)."""
    return jax.lax.dot_general(
        a.astype(jnp.int8), b.astype(jnp.int8), (dims, ((), ())),
        preferred_element_type=jnp.int32).astype(jnp.float32)


def _f32_dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _matmul_kernel(x_ref, w_ref, seed_ref, o_ref, acc_ref, *,
                   mantissa_bits, stochastic, quantize_w, block, bm, bk,
                   bn, n_k, K, N):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s = slice_width(block, bk)
    # sub-128 groups make the scales vary inside the MXU operand: those
    # mantissas dequantize in VMEM (exact in f32 for m ≤ 12) and contract
    # on the f32 MXU — the wgrad dataflow
    x_sub = small_groups(block, s)
    w_sub = small_groups(block, s, bn)
    cg = min(block or GROUP, bn)            # weight column group
    dims = ((1,), (0,))
    seed = seed_ref[0, 0] if stochastic else None
    i, j = pl.program_id(0), pl.program_id(1)
    acc = acc_ref[...]
    for c0 in range(0, bk, s):               # ascending exponent groups
        x = x_ref[:, c0:c0 + s].astype(jnp.float32)         # [bm, s]
        w = w_ref[c0:c0 + s, :].astype(jnp.float32)         # [s, bn]
        idx_x = idx_w = None
        if stochastic:
            r, c = _iota2((bm, s))
            idx_x = (i * bm + r) * K + (k * bk + c0 + c) + jnp.int32(STREAM_X)
            rw, cw = _iota2((s, bn))
            # offset w indices so x and w never share a stream position
            idx_w = ((k * bk + c0 + rw) * N + (j * bn + cw)
                     + jnp.int32(STREAM_W))
        qx, dx = quantize_block(x, mantissa_bits, row_group_amax(x, block),
                                stochastic=stochastic, seed=seed, idx=idx_x)
        if not quantize_w:
            # w is already narrow BFP (per-layer widths resolved by the
            # optimizer shell): y += (Qx·δx) @ w; δx factors out per row
            # unless sub-128 groups make it ride the contraction
            if x_sub:
                acc = acc + _f32_dot(qx * dx, w, dims)
            else:
                acc = acc + _f32_dot(qx, w, dims) * dx
        elif w_sub:
            qw, dw = quantize_block(w, mantissa_bits,
                                    tile_group_amax(w, block),
                                    stochastic=stochastic, seed=seed,
                                    idx=idx_w)
            acc = acc + _f32_dot(qx * dx, qw * dw, dims)
        else:
            # weight: one exponent per (s, cg) sub-tile; δw [1, bn]
            aw = spread(group_maxima(jnp.abs(w).max(axis=0, keepdims=True),
                                     cg, 1), cg, 1)
            qw, dw = quantize_block(w, mantissa_bits, aw,
                                    stochastic=stochastic, seed=seed,
                                    idx=idx_w)
            part = (_int_dot(qx, qw, dims) if mantissa_bits <= 8
                    # 12/16-bit mantissas: f32 MXU products of integral
                    # values are exact
                    else _f32_dot(qx, qw, dims))
            acc = acc + part * (dx * dw)     # δx [bm,1] · δw [1,bn]
    acc_ref[...] = acc

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("mantissa_bits", "stochastic",
                                             "quantize_w", "block",
                                             "bm", "bk", "bn",
                                             "interpret", "out_dtype"))
def hbfp_matmul_pallas(x, w, seed=None, *, mantissa_bits: int = 8,
                       stochastic: bool = False, quantize_w: bool = True,
                       block: int = 0,
                       bm: int = 128, bk: int = 128, bn: int = 128,
                       out_dtype=jnp.float32, interpret: bool = False):
    """Fused quantize+matmul. x: [M, K] f32/bf16, w: [K, N]. Shapes must be
    block-divisible (ops.py pads), and tile edges whole exponent groups.
    Returns [M, N] out_dtype.

    quantize_w=False skips the in-kernel weight quantization (w is already
    narrow BFP from the optimizer shell, possibly at per-layer widths the
    kernel must not crush) — f32 MXU path, since raw-valued w has no shared
    mantissa scale to contract in fixed point."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    if M % bm or K % bk or N % bn:
        raise ValueError(f"({M},{K})x({K},{N}) not divisible by "
                         f"({bm},{bk},{bn})")
    check_slices(block, bk, bn)
    if seed is None:
        seed = jnp.zeros((1, 1), jnp.int32)
    n_k = K // bk
    kernel = functools.partial(_matmul_kernel, mantissa_bits=mantissa_bits,
                               stochastic=stochastic, quantize_w=quantize_w,
                               block=block, bm=bm, bk=bk, bn=bn, n_k=n_k,
                               K=K, N=N)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=_params(bm, bk, bn, x.dtype.itemsize),
        interpret=interpret,
        name="hbfp_matmul_fwd",
    )(x, w, seed)


# ----------------------------------------------------------------------------
# dgrad: dx = Q(dy) · Q(w)^T — same structure as the forward, contracting
# over N. w blocks are read in their natural [bk, bn] layout and contracted
# on their N axis (dimension numbers transpose; nothing moves in HBM).
# ----------------------------------------------------------------------------

def _dgrad_kernel(g_ref, w_ref, seed_ref, o_ref, acc_ref, *,
                  mantissa_bits, stochastic, quantize_w, block, bm, bk,
                  bn, n_n, K, N):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s = slice_width(block, bn)
    g_sub = small_groups(block, s)
    w_sub = small_groups(block, s, bk)
    rg = min(block or GROUP, bk)            # weight row group
    dims = ((1,), (1,))
    seed = seed_ref[0, 0] if stochastic else None
    i, j = pl.program_id(0), pl.program_id(1)
    acc = acc_ref[...]
    for c0 in range(0, bn, s):               # ascending exponent groups
        g = g_ref[:, c0:c0 + s].astype(jnp.float32)         # [bm, s]
        w = w_ref[:, c0:c0 + s].astype(jnp.float32)         # [bk, s]
        idx_g = idx_w = None
        if stochastic:
            r, c = _iota2((bm, s))
            idx_g = (i * bm + r) * N + (n * bn + c0 + c) + jnp.int32(STREAM_G)
            rw, cw = _iota2((bk, s))
            # w's global element index — the same stream as the forward, so
            # the same exponent groups re-quantize w to identical draws
            idx_w = ((j * bk + rw) * N + (n * bn + c0 + cw)
                     + jnp.int32(STREAM_W))
        # gradient: activation semantics — one exponent per row per group
        qg, dg = quantize_block(g, mantissa_bits, row_group_amax(g, block),
                                stochastic=stochastic, seed=seed, idx=idx_g)
        if not quantize_w:
            if g_sub:
                acc = acc + _f32_dot(qg * dg, w, dims)
            else:
                acc = acc + _f32_dot(qg, w, dims) * dg
        elif w_sub:
            # sub-128 groups ride the contraction: dequantize in VMEM, f32
            # MXU (see the forward kernel)
            qw, dw = quantize_block(w, mantissa_bits,
                                    tile_group_amax(w, block),
                                    stochastic=stochastic, seed=seed,
                                    idx=idx_w)
            acc = acc + _f32_dot(qg * dg, qw * dw, dims)
        else:
            # weight: one exponent per (rg, s) sub-tile, quantized along
            # w's rows ([bk, 1]) and rescaling dx's columns ([1, bk])
            ms = group_maxima(jnp.abs(w).max(axis=1, keepdims=True), rg, 0)
            qw, _ = quantize_block(w, mantissa_bits, spread(ms, rg, 0),
                                   stochastic=stochastic, seed=seed,
                                   idx=idx_w)
            dw = bfp_step(spread(ms, rg, 1), mantissa_bits)
            part = (_int_dot(qg, qw, dims) if mantissa_bits <= 8
                    else _f32_dot(qg, qw, dims))
            acc = acc + part * (dg * dw)     # δg [bm,1] · δw [1,bk]
    acc_ref[...] = acc

    @pl.when(n == n_n - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("mantissa_bits", "stochastic",
                                             "quantize_w", "block",
                                             "bm", "bk", "bn",
                                             "interpret", "out_dtype"))
def hbfp_dgrad_pallas(g, w, seed=None, *, mantissa_bits: int = 8,
                      stochastic: bool = False, quantize_w: bool = True,
                      block: int = 0,
                      bm: int = 128, bk: int = 128, bn: int = 128,
                      out_dtype=jnp.float32, interpret: bool = False):
    """dx[M,K] = Q(g)[M,N] · Q(w)[K,N]^T. Tiles: bm over M (dx rows), bk
    over K (dx cols), bn over the contracted N axis."""
    M, N = g.shape
    K, N2 = w.shape
    assert N == N2, (g.shape, w.shape)
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    if M % bm or K % bk or N % bn:
        raise ValueError(f"dgrad ({M},{N})x({K},{N}) not divisible by "
                         f"({bm},{bk},{bn})")
    check_slices(block, bk, bn)
    if seed is None:
        seed = jnp.zeros((1, 1), jnp.int32)
    n_n = N // bn
    kernel = functools.partial(_dgrad_kernel, mantissa_bits=mantissa_bits,
                               stochastic=stochastic, quantize_w=quantize_w,
                               block=block, bm=bm, bk=bk, bn=bn, n_n=n_n,
                               K=K, N=N)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, K // bk, n_n),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, n: (i, n)),
            pl.BlockSpec((bk, bn), lambda i, j, n: (j, n)),
            pl.BlockSpec((1, 1), lambda i, j, n: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, j, n: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, K), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
        compiler_params=_params(bm, bn, bk, g.dtype.itemsize),
        interpret=interpret,
        name="hbfp_matmul_dgrad",
    )(g, w, seed)


# ----------------------------------------------------------------------------
# wgrad: dw = Σ_tokens x̂ ⊗ ĝ — contraction over the token axis M, where
# the per-training-input exponents live. δx[m]·δg[m] varies along the
# contraction, so the scales can't factor out of an integer dot: mantissas
# are rescaled in VMEM (q·δ is exact in f32 for m ≤ 12) and contracted on
# the f32 MXU — exactly the paper's FP accumulation of BFP outer products.
# ----------------------------------------------------------------------------

def _wgrad_kernel(x_ref, g_ref, seed_ref, o_ref, acc_ref, *,
                  mantissa_bits, stochastic, block, bm, bk, bn, n_m, K, N):
    m = pl.program_id(2)

    @pl.when(m == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)          # [bm, bk]
    g = g_ref[...].astype(jnp.float32)          # [bm, bn]

    seed = idx_x = idx_g = None
    if stochastic:
        seed = seed_ref[0, 0]
        i, j = pl.program_id(0), pl.program_id(1)
        r, c = _iota2((bm, bk))
        # x's global element index — the forward's stream, so the same
        # exponent groups reproduce the forward's quantization bit-for-bit
        idx_x = (m * bm + r) * K + (i * bk + c) + jnp.int32(STREAM_X)
        rg, cg = _iota2((bm, bn))
        idx_g = (m * bm + rg) * N + (j * bn + cg) + jnp.int32(STREAM_G)

    # per-token exponents per exponent group of the feature axis (x over
    # K, g over N), dequantized in VMEM: per-token scales ride the
    # contraction axis
    xh = dequantize_rows(x, mantissa_bits, block, stochastic=stochastic,
                         seed=seed, idx=idx_x)
    gh = dequantize_rows(g, mantissa_bits, block, stochastic=stochastic,
                         seed=seed, idx=idx_g)
    acc_ref[...] += _f32_dot(xh, gh, ((0,), (0,)))      # [bk, bn]

    @pl.when(m == n_m - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("mantissa_bits", "stochastic",
                                             "block", "bm", "bk", "bn",
                                             "interpret", "out_dtype"))
def hbfp_wgrad_pallas(x, g, seed=None, *, mantissa_bits: int = 8,
                      stochastic: bool = False, block: int = 0,
                      bm: int = 128, bk: int = 128, bn: int = 128,
                      out_dtype=jnp.float32, interpret: bool = False):
    """dw[K,N] = Q(x)[M,K]^T · Q(g)[M,N]. Tiles: bk over K (dw rows), bn
    over N (dw cols), bm over the contracted token axis M."""
    M, K = x.shape
    M2, N = g.shape
    assert M == M2, (x.shape, g.shape)
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    if M % bm or K % bk or N % bn:
        raise ValueError(f"wgrad ({M},{K})x({M},{N}) not divisible by "
                         f"({bm},{bk},{bn})")
    check_slices(block, bk, bn)
    if seed is None:
        seed = jnp.zeros((1, 1), jnp.int32)
    n_m = M // bm
    kernel = functools.partial(_wgrad_kernel, mantissa_bits=mantissa_bits,
                               stochastic=stochastic, block=block,
                               bm=bm, bk=bk, bn=bn, n_m=n_m, K=K, N=N)
    return pl.pallas_call(
        kernel,
        grid=(K // bk, N // bn, n_m),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, m: (m, i)),
            pl.BlockSpec((bm, bn), lambda i, j, m: (m, j)),
            pl.BlockSpec((1, 1), lambda i, j, m: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bk, bn), lambda i, j, m: (i, j)),
        out_shape=jax.ShapeDtypeStruct((K, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
        compiler_params=_params(bk, bm, bn, 4),
        interpret=interpret,
        name="hbfp_matmul_wgrad",
    )(x, g, seed)
