"""Jit'd public wrappers around the Pallas kernels: padding to block
multiples, batching, autotuned tile resolution, and CPU (interpret) / TPU
dispatch.

Off a TPU the kernels run through the Pallas interpreter; on a TPU the
same call sites compile to Mosaic. `interpret()` decides at trace time, so
importing this module touches no JAX backend, and on a TPU a kernel that
Mosaic refuses raises instead of falling back.

Tile sizes: pass bm/bk/bn explicitly to pin them, or leave None and the
wrapper resolves them at trace time from the autotuner table
(kernels/autotune.py; the shape rule `shape_tiles` when the shape is
untuned). Tiles change speed, not the exponent groups.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels.bfp_quantize import bfp_quantize_pallas
from repro.kernels.hbfp_matmul import (hbfp_dgrad_pallas, hbfp_matmul_pallas,
                                       hbfp_wgrad_pallas)


def interpret() -> bool:
    """True unless the default JAX backend is a TPU. Read at trace time by
    every kernel call site (never at import)."""
    return jax.default_backend() != "tpu"


def _pad_to(x, mults):
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mults)]
    if any(p[1] for p in pads):
        return jnp.pad(x, pads), True
    return x, False


def _tiles(op, bm, bk, bn, M, K, N, mantissa_bits, dtype="float32",
           block=0):
    if bm is None or bk is None or bn is None:
        t = autotune.lookup(op, M, K, N, dtype=dtype,
                            mantissa_bits=mantissa_bits, block=block)
        bm, bk, bn = (t[0] if bm is None else bm, t[1] if bk is None else bk,
                      t[2] if bn is None else bn)
    return autotune.clip_tiles((bm, bk, bn), M, K, N)


def bfp_quantize(x, seed=0, *, mantissa_bits=8, tile=128, stochastic=False,
                 with_stats=False):
    """Quantize a 2-D array to packed BFP via the Pallas conversion kernel.

    Returns (mantissa [R, C], per-tile exponent grid); the kernel zero-pads
    non-tile-divisible shapes internally and slices the mantissas back.
    with_stats=True appends an aggregate stats dict (fused outputs of the
    same kernel pass, DESIGN.md §9): element clip count, clip fraction, and
    the exponent min/max/spread across tiles.
    """
    assert x.ndim == 2
    seed = jnp.full((1, 1), seed, jnp.int32)
    out = bfp_quantize_pallas(x, seed, mantissa_bits=mantissa_bits,
                              tile_r=tile, tile_c=tile,
                              stochastic=stochastic, with_stats=with_stats,
                              interpret=interpret())
    if not with_stats:
        return out
    m, e, clip_count, emin, emax = out
    stats = {"clip_count": clip_count.sum(),
             "clip_frac": clip_count.sum() / float(x.size),
             "exp_min": emin.min(), "exp_max": emax.max(),
             "exp_spread": emax.max() - emin.min()}
    return m, e, stats


def hbfp_matmul(x, w, seed=None, *, mantissa_bits=8, stochastic=False,
                quantize_w=True, block=0, bm=None, bk=None, bn=None):
    """Fused HBFP matmul for [..., M, K] @ [K, N] (leading dims flattened).

    Pads every dim to the tile size (zero rows/cols quantize to zero and
    contribute nothing), calls the kernel, slices back. Tiles default to
    the autotuner table for the logical shape. `block` (0 ⇒ groups of
    128) selects the exponent-block granularity (DESIGN.md §13) and keys
    its own autotune cell.
    """
    lead = x.shape[:-2] if x.ndim > 2 else ()
    M0, K0 = x.shape[-2], x.shape[-1]
    N0 = w.shape[-1]
    x2 = x.reshape(-1, K0)
    bm, bk, bn = _tiles("matmul_fwd", bm, bk, bn, x2.shape[0], K0, N0,
                        mantissa_bits, str(x.dtype), block)
    xp, _ = _pad_to(x2, (bm, bk))
    wp, _ = _pad_to(w, (bk, bn))
    seed_arr = None if seed is None else jnp.full((1, 1), seed, jnp.int32)
    y = hbfp_matmul_pallas(xp, wp, seed_arr, mantissa_bits=mantissa_bits,
                           stochastic=stochastic, quantize_w=quantize_w,
                           block=block, bm=bm, bk=bk, bn=bn,
                           interpret=interpret())
    y = y[:x2.shape[0], :N0]
    return y.reshape(*lead, M0, N0)


def hbfp_dgrad(g, w, seed=None, *, mantissa_bits=8, stochastic=False,
               quantize_w=True, block=0, bm=None, bk=None, bn=None):
    """Fused dgrad dx[M,K] = Q(g)[M,N]·Q(w)[K,N]^T with pad-and-slice."""
    M0, N0 = g.shape
    K0 = w.shape[0]
    bm, bk, bn = _tiles("matmul_dgrad", bm, bk, bn, M0, K0, N0,
                        mantissa_bits, str(g.dtype), block)
    gp, _ = _pad_to(g, (bm, bn))
    wp, _ = _pad_to(w, (bk, bn))
    seed_arr = None if seed is None else jnp.full((1, 1), seed, jnp.int32)
    dx = hbfp_dgrad_pallas(gp, wp, seed_arr, mantissa_bits=mantissa_bits,
                           stochastic=stochastic, quantize_w=quantize_w,
                           block=block, bm=bm, bk=bk, bn=bn,
                           interpret=interpret())
    return dx[:M0, :K0]


def hbfp_wgrad(x, g, seed=None, *, mantissa_bits=8, stochastic=False,
               block=0, bm=None, bk=None, bn=None):
    """Fused wgrad dw[K,N] = Q(x)[M,K]^T·Q(g)[M,N] with pad-and-slice."""
    M0, K0 = x.shape
    N0 = g.shape[1]
    bm, bk, bn = _tiles("matmul_wgrad", bm, bk, bn, M0, K0, N0,
                        mantissa_bits, str(x.dtype), block)
    xp, _ = _pad_to(x, (bm, bk))
    gp, _ = _pad_to(g, (bm, bn))
    seed_arr = None if seed is None else jnp.full((1, 1), seed, jnp.int32)
    dw = hbfp_wgrad_pallas(xp, gp, seed_arr, mantissa_bits=mantissa_bits,
                           stochastic=stochastic, block=block,
                           bm=bm, bk=bk, bn=bn, interpret=interpret())
    return dw[:K0, :N0]
