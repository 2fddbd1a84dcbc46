"""Pallas TPU kernel: FP32 → packed BFP conversion (the paper's "FP-to-BFP
unit", §5.3: detect the max exponent of the incoming tensor and normalize
mantissas, with xorshift stochastic rounding during truncation).

TPU adaptation: one grid program converts one VMEM-resident (block_r ×
block_c) slab; exponent-sharing tiles (tile_r × tile_c) subdivide the slab
(tile edges aligned to the 8×128 VREG lanes when tile ≥ 128). Outputs packed
mantissas (int8 for m ≤ 8 else int16) and one int8 exponent per tile — the
storage format that realizes the paper's 2× model compression and the 4×
forward/backward bandwidth saving.

Non-divisible shapes are padded with zeros to tile multiples inside the
wrapper and the mantissas sliced back (zeros quantize to zero and never
raise a tile amax, so real elements are unaffected; fully-padded tiles get
the EXP_FLOOR exponent). `with_stats=True` adds fused fidelity outputs in
the same pass — per-tile saturation counts and per-block exponent min/max —
feeding the numerics observatory (DESIGN.md §9) without a second read of
the tensor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import quantize_block


def _quantize_kernel(x_ref, seed_ref, *out_refs, mantissa_bits,
                     tile_r, tile_c, stochastic, block_r, block_c, n_cols,
                     with_stats):
    if with_stats:
        mant_ref, exp_ref, clip_ref, emin_ref, emax_ref = out_refs
    else:
        mant_ref, exp_ref = out_refs
    nr, nc = block_r // tile_r, block_c // tile_c
    i, j = pl.program_id(0), pl.program_id(1)
    seed = seed_ref[0, 0] if stochastic else None
    mdt = jnp.int8 if mantissa_bits <= 8 else jnp.int16
    # per-tile results gather into [nr, nc] grids by select: each tile is
    # a static, tile-aligned slice of the slab (Mosaic cannot reshape the
    # slab into tile groups)
    ti = jax.lax.broadcasted_iota(jnp.int32, (nr, nc), 0)
    tj = jax.lax.broadcasted_iota(jnp.int32, (nr, nc), 1)
    exps = jnp.zeros((nr, nc), jnp.int32)
    clips = jnp.zeros((nr, nc), jnp.int32)
    for a in range(nr):
        for b in range(nc):
            rows = pl.ds(a * tile_r, tile_r)
            cols = pl.ds(b * tile_c, tile_c)
            x = x_ref[rows, cols].astype(jnp.float32)
            idx = None
            if stochastic:
                r = jax.lax.broadcasted_iota(jnp.int32, (tile_r, tile_c), 0)
                c = jax.lax.broadcasted_iota(jnp.int32, (tile_r, tile_c), 1)
                idx = ((i * block_r + a * tile_r + r) * n_cols
                       + (j * block_c + b * tile_c + c))
            q, delta, clipped = quantize_block(
                x, mantissa_bits, jnp.abs(x).max(keepdims=True),
                stochastic=stochastic, seed=seed, idx=idx, with_clip=True)
            mant_ref[rows, cols] = q.astype(mdt)
            dbits = jax.lax.bitcast_convert_type(delta, jnp.int32)
            e = ((dbits >> 23) & 0xFF) - 127 + (mantissa_bits - 2)
            here = (ti == a) & (tj == b)
            exps = jnp.where(here, e, exps)
            if with_stats:
                n_clip = clipped.astype(jnp.int32).sum(keepdims=True)
                clips = jnp.where(here, n_clip, clips)
    exp_ref[0, 0] = exps
    if with_stats:
        clip_ref[0, 0] = clips
        emin_ref[0, 0] = exps.min(keepdims=True)
        emax_ref[0, 0] = exps.max(keepdims=True)


def _fit_block(n_tiles: int, want_tiles: int) -> int:
    """Largest tile count ≤ want_tiles that divides n_tiles (≥ 1)."""
    k = max(1, min(want_tiles, n_tiles))
    while n_tiles % k:
        k -= 1
    return k


@functools.partial(jax.jit, static_argnames=("mantissa_bits", "tile_r",
                                             "tile_c", "stochastic",
                                             "block_r", "block_c",
                                             "with_stats", "interpret"))
def bfp_quantize_pallas(x, seed, *, mantissa_bits: int = 8,
                        tile_r: int = 128, tile_c: int = 128,
                        stochastic: bool = False,
                        block_r: int = 256, block_c: int = 512,
                        with_stats: bool = False,
                        interpret: bool = False):
    """Pack a 2-D f32 array into BFP (mantissa, per-tile exponent).

    x: [R, C], any shape — non-tile-divisible inputs are zero-padded to
    tile multiples and the mantissas sliced back to [R, C] (the exponent
    grid stays at the padded ceil(R/tile_r) × ceil(C/tile_c) resolution).
    seed: int32 scalar array (stochastic rounding stream id).
    Returns (mantissa [R, C] int8/int16, exponent grid int8); with
    with_stats=True additionally (clip_count per tile int32, exp_min,
    exp_max per block int32) fused into the same pass.
    """
    R, C = x.shape
    tr, tc = min(tile_r, R), min(tile_c, C)
    Rp, Cp = -(-R // tr) * tr, -(-C // tc) * tc
    if (Rp, Cp) != (R, C):
        x = jnp.pad(x, ((0, Rp - R), (0, Cp - C)))
    block_r = tr * _fit_block(Rp // tr, max(min(block_r, Rp) // tr, 1))
    block_c = tc * _fit_block(Cp // tc, max(min(block_c, Cp) // tc, 1))
    mdt = jnp.int8 if mantissa_bits <= 8 else jnp.int16
    grid = (Rp // block_r, Cp // block_c)
    nr, nc = block_r // tr, block_c // tc
    kernel = functools.partial(
        _quantize_kernel, mantissa_bits=mantissa_bits, tile_r=tr, tile_c=tc,
        stochastic=stochastic, block_r=block_r, block_c=block_c, n_cols=Cp,
        with_stats=with_stats)
    # per-slab grids are [gr, gc, nr, nc] (their block's last two dims are
    # the whole array's, which Mosaic's tiling rule accepts) and are laid
    # out as the [Rp/tr, Cp/tc] tile grid after the call
    per_tile = pl.BlockSpec((1, 1, nr, nc), lambda i, j: (i, j, 0, 0))
    per_slab = pl.BlockSpec((1, 1, 1, 1), lambda i, j: (i, j, 0, 0))
    out_specs = [pl.BlockSpec((block_r, block_c), lambda i, j: (i, j)),
                 per_tile]
    out_shape = [jax.ShapeDtypeStruct((Rp, Cp), mdt),
                 jax.ShapeDtypeStruct(grid + (nr, nc), jnp.int32)]
    if with_stats:
        out_specs += [per_tile, per_slab, per_slab]
        out_shape += [jax.ShapeDtypeStruct(grid + (nr, nc), jnp.int32),
                      jax.ShapeDtypeStruct(grid + (1, 1), jnp.int32),
                      jax.ShapeDtypeStruct(grid + (1, 1), jnp.int32)]
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_r, block_c), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),  # seed scalar
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="bfp_quantize",
    )(x, seed)

    def tile_grid(t):
        return t.transpose(0, 2, 1, 3).reshape(Rp // tr, Cp // tc)

    mant = out[0][:R, :C]
    exps = tile_grid(out[1]).astype(jnp.int8)
    if not with_stats:
        return mant, exps
    return (mant, exps, tile_grid(out[2]), out[3].reshape(grid),
            out[4].reshape(grid))
