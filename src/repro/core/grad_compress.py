"""BFP-compressed data-parallel gradient reduction (beyond-paper).

The paper's conclusion: BFP "leads to … lower communication bandwidth
requirements for distributed training". We realize that for the DP gradient
all-reduce: inside a shard_map over the data axis, gradients are packed to
int8 BFP mantissas (+1 int8 exponent per tile), all-gathered as int8, and
dequantized+summed locally. Wire bytes per device drop from
≈ 2·4·S·(N-1)/N (f32 ring all-reduce) to ≈ (S + S/tile)·(N-1)/N (int8
all-gather) — ~7.5× fewer collective bytes at N=16 (measured in the §Perf
iteration log from the lowered HLO).

Error feedback (residual accumulation, Karimireddy et al.-style) makes the
compression unbiased across steps: the quantization error of step t is added
back into the gradient at step t+1, so the *sum* of transmitted gradients
tracks the true sum.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import bfp
from repro.core.formats import HBFPConfig

COMPRESS_TILE = 512  # exponent-sharing group for gradient vectors


def _flat_tile(g):
    return (COMPRESS_TILE,) if g.ndim == 1 else (1,) * (g.ndim - 1) + (COMPRESS_TILE,)


def compress(g: jax.Array, mantissa_bits: int = 8):
    """g -> (int8/int16 mantissa, int8 exponent per tile)."""
    return bfp.pack(g, mantissa_bits, _flat_tile(g))


def decompress(p) -> jax.Array:
    return bfp.unpack(p)


def compressed_psum_tree(grads, axis_name: str, *,
                         mantissa_bits: int = 8,
                         residual=None) -> Tuple[object, object]:
    """All-reduce a gradient pytree over `axis_name` in BFP-compressed form.

    Must be called inside shard_map with `axis_name` manual. Returns
    (mean-reduced grads, new residual pytree for error feedback).
    """
    n = jax.lax.axis_size(axis_name)

    def one(g, r):
        gf = g.astype(jnp.float32)
        if r is not None:
            gf = gf + r
        p = compress(gf, mantissa_bits)
        new_r = gf - decompress(p)
        # all-gather the packed int8 payload; dequantize + sum locally.
        gm = jax.lax.all_gather(p.mantissa, axis_name)        # [N, ...] int8
        ge = jax.lax.all_gather(p.exponent, axis_name)        # [N, ...] int8
        stacked = bfp.PackedBFP(gm, ge, p.mantissa_bits,
                                (1,) + p.tile_shape, (n,) + p.shape)
        total = decompress(stacked).sum(axis=0) / n
        return total.astype(g.dtype), new_r

    if residual is None:
        residual = jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32),
                                grads)
    out = jax.tree.map(one, grads, residual)
    new_grads = jax.tree.map(lambda t: t[0], out,
                             is_leaf=lambda t: isinstance(t, tuple))
    new_res = jax.tree.map(lambda t: t[1], out,
                           is_leaf=lambda t: isinstance(t, tuple))
    return new_grads, new_res
