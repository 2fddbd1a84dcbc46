"""Shared kernel helpers: exponent extraction and the paper's xorshift RNG.

These are written in plain jnp so the Pallas kernel bodies and the ref.py
oracles share the *same* code — nearest-rounding results are bit-exact between
kernel and oracle, and stochastic-rounding results are too (same counter-based
xorshift stream).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EXP_FLOOR = -100
EXP_CEIL = 126

# Stochastic-rounding stream offsets: each operand of the three training
# GEMMs draws from a disjoint region of the counter-based xorshift stream,
# keyed by the GLOBAL element index (row * row_stride + col) plus the
# operand's offset. Re-quantizing the same tensor in another GEMM (x in
# fwd and wgrad, g in dgrad and wgrad) therefore replays the identical
# draws — "quantize once, use everywhere" without materializing the
# quantized copy (see docs/KERNELS.md).
STREAM_X = 0x00000000
STREAM_G = 0x20000000
STREAM_W = 0x40000000

# Per-GEMM-role seed salts (DESIGN.md §11): with per-role mantissa widths
# (PrecisionPolicy role_widths, e.g. "wgrad+2") a tensor is quantized at
# DIFFERENT widths in different GEMMs. The element-index streams above are
# shared by design — same width ⇒ identical draws ("quantize once, use
# everywhere") — but a role running at its own width must not consume
# another role's stream positions, or the two quantizations become
# correlated through the shared uniforms. `role_stream_salt` returns 0 at
# the base width (preserving the replay property bit-for-bit) and a
# (role, width)-specific seed salt otherwise.
ROLE_STREAM_SALT = {
    "fwd": 0x00000000,          # the base stream: never salted
    "dgrad": 0x1B873593,        # murmur3 c2
    "wgrad": 0x6A09E667,        # frac(sqrt(2)) — sha-2 IV
    "attn_qk": 0x3C6EF372,      # frac(sqrt(3))
    "attn_pv": 0x510E527F,      # frac(sqrt(5))
}


def role_stream_salt(role: str, m_bits: int, base_bits: int,
                     block: int = 0, base_block: int = 0) -> int:
    """Seed salt for quantizing one operand in GEMM role `role` at width
    `m_bits` / exponent-block size `block` when the policy's base (fwd)
    format is (`base_bits`, `base_block`). 0 ⇒ use the unsalted stream
    (identical draws to the fwd quantization of the same tensor); nonzero
    ⇒ a disjoint counter stream for this (role, width, block). A diverged
    block size salts even at the base width — a tensor re-quantized at a
    different block granularity must not consume another site's draws
    (DESIGN.md §13, the same hazard PR 4 fixed for role widths)."""
    if m_bits == base_bits and int(block) == int(base_block):
        return 0
    salt = ROLE_STREAM_SALT[role] ^ (m_bits * 0x9E3779B9)
    if int(block) != int(base_block):
        salt ^= (int(block) + 1) * 0x85EBCA6B  # murmur3 c1
    return salt & 0x7FFFFFFF


def max_exponent(amax: jax.Array) -> jax.Array:
    """floor(log2 amax) by f32 bit-field extraction (kernel-safe)."""
    bits = jax.lax.bitcast_convert_type(amax.astype(jnp.float32), jnp.int32)
    e = ((bits >> 23) & 0xFF) - 127
    return jnp.clip(e, EXP_FLOOR, EXP_CEIL)


def xorshift32(x: jax.Array) -> jax.Array:
    """One round of Marsaglia xorshift32 (paper §5.3 uses this RNG for
    stochastic rounding: 'three constant shifts and three xor operations')."""
    x = x ^ (x << 13)
    x = x ^ ((x >> 17) & 0x7FFF)  # logical shift on int32
    x = x ^ (x << 5)
    return x


def uniform_from_index(seed: jax.Array, idx: jax.Array) -> jax.Array:
    """Counter-based U[0,1) stream: hash (seed, element-index) through two
    xorshift rounds. idx must be int32 and unique per element."""
    golden = jnp.int32(-1640531527)  # 0x9E3779B9 as two's-complement int32
    s = (idx * golden) ^ seed.astype(jnp.int32)
    s = xorshift32(xorshift32(s | jnp.int32(1)))
    # take 24 high-ish bits -> [0, 1)
    u = ((s >> 7) & 0x00FFFFFF).astype(jnp.float32) * (1.0 / 16777216.0)
    return u


def pow2(e):
    """Exact 2^e via IEEE-754 bit construction (see core.bfp.pow2)."""
    bits = (e.astype(jnp.int32) + 127) << 23
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _group_max(a, block: int, axis: int):
    """Max of the non-negative 2-D `a` over `block`-sized groups along
    `axis`, broadcast back to a's shape. Built from masked full-tile
    reductions rather than a reshape into groups: Mosaic cannot split a
    vreg's lane (or sublane) axis into sub-128 groups, and max is exact,
    so the result is bit-identical to the reshape form."""
    grp = jax.lax.broadcasted_iota(jnp.int32, a.shape, axis) // block
    out = jnp.zeros_like(a)
    for g in range(a.shape[axis] // block):
        m = grp == g
        gmax = jnp.where(m, a, 0.0).max(axis=axis, keepdims=True)
        out = jnp.where(m, gmax, out)
    return out


def row_group_amax(x, block: int):
    """Per-row |x| max over `block`-sized groups of the last axis — the
    activation/gradient exponent granularity inside one kernel tile
    (DESIGN.md §13). block=0 (or ≥ the row length) ⇒ one amax per whole
    row, today's per-row-block exponent. Groups clamp to the row length
    exactly like `bfp._tile_view` clamps tile dims, so the kernel matches
    the sim backend bit-for-bit on aligned shapes. Returns an array
    broadcastable against x."""
    a = jnp.abs(x)
    c = x.shape[1]
    if not block or block >= c:
        return a.max(axis=1, keepdims=True)
    if c % block:
        raise ValueError(f"block {block} must divide the tile K edge {c}")
    return _group_max(a, block, 1)


def tile_group_amax(w, block: int):
    """|w| max over (block, block) sub-tiles of one 2-D kernel tile — the
    weight exponent granularity (DESIGN.md §13). block=0 ⇒ one amax for
    the whole tile (today's semantics), kept 2-D ([1, 1]) because Mosaic
    cannot bitcast a 0-d scalar; block clamps per-dim to the tile edges
    like `bfp._tile_view`. Returns an array broadcastable against w."""
    a = jnp.abs(w)
    if not block:
        return a.max(keepdims=True)
    r, c = w.shape
    rb, cb = min(block, r), min(block, c)
    if r % rb or c % cb:
        raise ValueError(f"block {block} must divide tile edges {(r, c)}")
    return _group_max(_group_max(a, cb, 1), rb, 0)


# The GEMM kernels' exponent group at block=0 (DESIGN.md §13): one exponent
# per row per GROUP contraction columns for activations and gradients, one
# per GROUP x GROUP sub-tile for weights, whatever the kernel tile (a tile
# edge under GROUP is one group). Tiles are a speed choice only.
GROUP = 128


def slice_width(block: int, edge: int) -> int:
    """Contraction columns a GEMM kernel quantizes and contracts per inner
    step of a tile edge: one exponent group (`block`, or GROUP at
    block=0) when the group is at least GROUP wide, else GROUP columns
    holding several smaller groups; never more than the edge."""
    return min(max(int(block) or GROUP, GROUP), edge)


def small_groups(block: int, *edges: int) -> bool:
    """True when a sub-GROUP `block` splits one of these slice edges into
    several exponent groups: the scales then vary inside the MXU operand,
    so the kernels dequantize in VMEM and contract on the f32 MXU."""
    return bool(block) and block < GROUP and any(block < e for e in edges)


def check_slices(block: int, *edges: int) -> None:
    """Each tile edge must hold whole contraction slices (ValueError)."""
    for e in edges:
        if e % slice_width(block, e):
            raise ValueError(f"tile edge {e} is not a whole number of "
                             f"{slice_width(block, e)}-wide exponent "
                             f"groups (block={block})")


def group_maxima(a, group: int, axis: int):
    """Per-group max of the non-negative vector `a` ([1, C] for axis=1,
    [R, 1] for axis=0) over consecutive `group`-long runs, as a list of
    [1, 1] arrays — one per weight sub-tile of a contraction slice, from
    static aligned slices (no masked reductions)."""
    n = a.shape[axis]
    if group >= n:
        return [a.max(axis=axis, keepdims=True)]
    return [jax.lax.slice_in_dim(a, o, o + group, axis=axis)
            .max(axis=axis, keepdims=True) for o in range(0, n, group)]


def spread(maxima, group: int, axis: int):
    """`group_maxima`'s list broadcast back along `axis` ([1, n·group] for
    axis=1, [n·group, 1] for axis=0); one group stays [1, 1]."""
    if len(maxima) == 1:
        return maxima[0]
    shape = (1, group) if axis == 1 else (group, 1)
    return jnp.concatenate([jnp.broadcast_to(m, shape) for m in maxima],
                           axis=axis)


def bfp_step(amax, mantissa_bits: int):
    """The quantization step δ for a (broadcastable) amax, exactly as
    `quantize_block` computes it."""
    return pow2(max_exponent(amax) - mantissa_bits + 2)


def dequantize_rows(x, mantissa_bits: int, block: int, *, stochastic: bool,
                    seed=None, idx=None):
    """Q(x)·δ for a 2-D activation or gradient block: one exponent per row
    per exponent group of the last axis, taken slice by slice
    (`slice_width`; sub-GROUP blocks refine inside each slice). Values
    are exact in f32 for m ≤ 12 — the wgrad kernel's operand format."""
    s = slice_width(block, x.shape[1])
    parts = []
    for o in range(0, x.shape[1], s):
        xs = x[:, o:o + s]
        q, d = quantize_block(xs, mantissa_bits, row_group_amax(xs, block),
                              stochastic=stochastic, seed=seed,
                              idx=None if idx is None else idx[:, o:o + s])
        parts.append(q * d)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def quantize_block(x, mantissa_bits: int, amax, *, stochastic: bool,
                   seed=None, idx=None, with_clip: bool = False):
    """Quantize x against per-element broadcastable amax. Returns (q, delta)
    with q integral-valued f32 (castable to int8/int16) and delta the step.
    with_clip=True additionally returns the bool saturation mask (elements
    whose rounded mantissa exceeded ±(2^(m-1)-1)) — the fused stat output of
    the conversion kernel (DESIGN.md §9)."""
    e = max_exponent(amax)
    delta = pow2(e - mantissa_bits + 2)
    v = x.astype(jnp.float32) / delta
    if stochastic:
        v = jnp.floor(v + uniform_from_index(seed, idx))
    else:
        v = jnp.rint(v)
    lim = float(2 ** (mantissa_bits - 1) - 1)
    q = jnp.clip(v, -lim, lim)
    if with_clip:
        return q, delta, jnp.abs(v) > lim
    return q, delta
