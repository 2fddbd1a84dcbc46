"""Pallas TPU kernel: fused HBFP matmul — the paper's MatMul unit (§5.3).

    y[M,N] = sum_k  ( Q_row(x)[M,K_k] · Q_tile(w)[K_k,N_n] ) · δx·δw

TPU adaptation of the paper's FPGA dataflow:
  * the BFP exponent-sharing tile IS the MXU block: activations get one
    exponent per row per K-block (the paper's "one exponent per training
    input", refined to the block so conversion fuses with the matmul);
    weights get one exponent per (bk × bn) block (the paper's square weight
    tiles, 128-aligned for the MXU instead of the FPGA's 24);
  * mantissas are contracted on the MXU — int8 path for m ≤ 8 (2× bf16
    throughput on v5e, the paper's "fixed-point logic"), exact-f32 path for
    8 < m ≤ 12;
  * per-tile partial products are rescaled by δx·δw and accumulated in an
    f32 VMEM scratch across the K grid dimension — the paper's "wide
    accumulators"/"tiles accumulated in floating point" (§4.2 Tiling), so
    the MatMul unit never overflows or saturates;
  * FP→BFP conversion happens in VMEM right before the MXU op (the paper's
    "convert to BFP right before dot products", §4), with in-kernel xorshift
    stochastic rounding.

Grid: (M/bm, N/bn, K/bk), K innermost ("arbitrary") so the accumulator
carries across K steps; M/N dims are parallel.

Backward GEMMs (docs/KERNELS.md, DESIGN.md §10): the paper's claim is that
*all three* training GEMMs run in BFP, so dgrad and wgrad are fused Pallas
kernels too, not autodiff through the forward:

  dgrad:  dx[M,K] = Q_row(dy)[M,N_n] · Q_tile(w)[K_k,N_n]^T  · δg·δw
  wgrad:  dw[K,N] = Σ_m  x̂[m,K_k] ⊗ ĝ[m,N_n]               (FP accumulate)

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

from repro.kernels.common import (STREAM_G, STREAM_W, STREAM_X,
                                  quantize_block, row_group_amax,
                                  tile_group_amax)


def _matmul_kernel(x_ref, w_ref, seed_ref, o_ref, acc_ref, *,
                   mantissa_bits, stochastic, quantize_w, block, bm, bk,
                   bn, n_k, K, N):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)          # [bm, bk]
    w = w_ref[...].astype(jnp.float32)          # [bk, bn]

    seed = idx_x = idx_w = None
    if stochastic:
        seed = seed_ref[0, 0]
        i, j = pl.program_id(0), pl.program_id(1)
        r = jax.lax.broadcasted_iota(jnp.int32, (bm, bk), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (bm, bk), 1)
        idx_x = (i * bm + r) * K + (k * bk + c) + jnp.int32(STREAM_X)
        rw = jax.lax.broadcasted_iota(jnp.int32, (bk, bn), 0)
        cw = jax.lax.broadcasted_iota(jnp.int32, (bk, bn), 1)
        # offset w indices so x and w never share a stream position
        idx_w = (k * bk + rw) * N + (j * bn + cw) + jnp.int32(STREAM_W)

    # activation: one exponent per row per block-group of the K-block
    # (block=0, or ≥ bk, ⇒ the whole row — today's semantics); δx then
    # varies along the contraction iff the group is finer than bk
    x_sub = bool(block) and block < bk
    w_sub = bool(block) and (block < bk or block < bn)
    ax = row_group_amax(x, block)
    qx, dx = quantize_block(x, mantissa_bits, ax, stochastic=stochastic,
                            seed=seed, idx=idx_x)
    if not quantize_w:
        # w is already narrow BFP (per-layer widths resolved by the
        # optimizer shell): y += (Qx·δx) @ w; δx factors out per row
        # unless sub-row groups make it ride the contraction
        if x_sub:
            part = jax.lax.dot_general(
                qx * dx, w, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_ref[...] += part
        else:
            part = jax.lax.dot_general(
                qx, w, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_ref[...] += part * dx
    else:
        # weight: one exponent per (block, block) sub-tile; block=0 or ≥
        # both tile edges ⇒ one exponent per (bk, bn) tile (the kernel's
        # coarsest granularity — b clamps to the tile, DESIGN.md §13)
        aw = tile_group_amax(w, block if w_sub else 0)
        qw, dw = quantize_block(w, mantissa_bits, aw, stochastic=stochastic,
                                seed=seed, idx=idx_w)
        if x_sub or w_sub:
            # sub-block exponents: the scales vary inside the tile, so
            # mantissas dequantize in VMEM (exact in f32 for m ≤ 12) and
            # contract on the f32 MXU — the wgrad dataflow
            part = jax.lax.dot_general(
                qx * dx, qw * dw, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_ref[...] += part
        else:
            if mantissa_bits <= 8:
                # fixed-point path: int8 mantissas on the MXU, exact int32
                # accumulate
                part = jax.lax.dot_general(
                    qx.astype(jnp.int8), qw.astype(jnp.int8),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32).astype(jnp.float32)
            else:
                # 12/16-bit mantissas: f32 MXU products of integral values
                # are exact
                part = jax.lax.dot_general(
                    qx, qw, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            acc_ref[...] += part * (dx * dw)    # δx [bm,1] · δw scalar

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
    block-divisible (ops.py pads). Returns [M, N] out_dtype.

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

    g = g_ref[...].astype(jnp.float32)          # [bm, bn]
    w = w_ref[...].astype(jnp.float32)          # [bk, bn]

    seed = idx_g = idx_w = None
    if stochastic:
        seed = seed_ref[0, 0]
        i, j = pl.program_id(0), pl.program_id(1)
        r = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
        idx_g = (i * bm + r) * N + (n * bn + c) + jnp.int32(STREAM_G)
        rw = jax.lax.broadcasted_iota(jnp.int32, (bk, bn), 0)
        cw = jax.lax.broadcasted_iota(jnp.int32, (bk, bn), 1)
        # w's global element index — the same stream as the forward, so a
        # matching tile partition re-quantizes w to identical draws
        idx_w = (j * bk + rw) * N + (n * bn + cw) + jnp.int32(STREAM_W)

    # gradient: activation semantics — one exponent per row per
    # block-group of the N-block (block=0 or ≥ bn ⇒ the whole row)
    g_sub = bool(block) and block < bn
    w_sub = bool(block) and (block < bk or block < bn)
    ag = row_group_amax(g, block)
    qg, dg = quantize_block(g, mantissa_bits, ag, stochastic=stochastic,
                            seed=seed, idx=idx_g)
    if not quantize_w:
        if g_sub:
            part = jax.lax.dot_general(
                qg * dg, w, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_ref[...] += part
        else:
            part = jax.lax.dot_general(
                qg, w, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_ref[...] += part * dg
    else:
        aw = tile_group_amax(w, block if w_sub else 0)
        qw, dw = quantize_block(w, mantissa_bits, aw, stochastic=stochastic,
                                seed=seed, idx=idx_w)
        if g_sub or w_sub:
            # sub-block exponents ride the contraction: dequantize in
            # VMEM, f32 MXU (see the forward kernel)
            part = jax.lax.dot_general(
                qg * dg, qw * dw, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_ref[...] += part
        elif mantissa_bits <= 8:
            part = jax.lax.dot_general(
                qg.astype(jnp.int8), qw.astype(jnp.int8),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32).astype(jnp.float32)
            acc_ref[...] += part * (dg * dw)
        else:
            part = jax.lax.dot_general(
                qg, qw, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_ref[...] += part * (dg * dw)

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
        r = jax.lax.broadcasted_iota(jnp.int32, (bm, bk), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (bm, bk), 1)
        # x's global element index — the forward's stream, so matching
        # K-blocking reproduces the forward's quantization bit-for-bit
        idx_x = (m * bm + r) * K + (i * bk + c) + jnp.int32(STREAM_X)
        rg = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
        cg = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
        idx_g = (m * bm + rg) * N + (j * bn + cg) + jnp.int32(STREAM_G)

    # per-token exponents, optionally refined to block-groups of the
    # feature axis (block=0 ⇒ the whole row — today's semantics)
    ax = row_group_amax(x, block)
    qx, dx = quantize_block(x, mantissa_bits, ax, stochastic=stochastic,
                            seed=seed, idx=idx_x)
    ag = row_group_amax(g, block)
    qg, dg = quantize_block(g, mantissa_bits, ag, stochastic=stochastic,
                            seed=seed, idx=idx_g)
    # dequantize in VMEM: per-token scales ride the contraction axis
    part = jax.lax.dot_general(
        qx * dx, qg * dg, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)     # [bk, bn]
    acc_ref[...] += part

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
        interpret=interpret,
        name="hbfp_matmul_wgrad",
    )(x, g, seed)
