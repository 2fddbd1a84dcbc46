"""Training-path HBFP matmul on the Pallas kernels (custom VJP).

`hbfp_matmul_kernel` is the kernel-backend counterpart of
`core.hbfp_ops.hbfp_matmul`: same semantics (all three training GEMMs in
BFP, gradients flow straight through the quantizers), but every GEMM is a
fused quantize-in-VMEM Pallas kernel instead of quantize ops + XLA matmul:

    fwd  : y  = Q_row(x) · Q_tile(w)        hbfp_matmul_pallas
    dgrad: dx = Q_row(dy) · Q_tile(w)^T     hbfp_dgrad_pallas
    wgrad: dw = Q_row(x)^T ⊙ Q_row(dy)      hbfp_wgrad_pallas (FP accumulate)

Each GEMM quantizes its operands at its own tiling right before the dot
(the paper's conversion-fused-into-MatMul rule; FlexBlock's per-GEMM BFP
modes) — x and dy draw from the same stochastic stream in every GEMM they
appear in (kernels/common.py STREAM_*), so matching tilings re-quantize to
identical values. Tile sizes resolve per GEMM at trace time through the
autotuner table, else the shape rule (kernels/autotune.py); they change
speed only, never the exponent groups (kernels/hbfp_matmul.py). Each
resolution emits a `kernel/gemm` event (op, shape, tiles, path) to the
recorder of `gemm_events`. Non-divisible shapes pad to the tile grid and
slice back; zero padding quantizes to zero and contributes nothing to any
of the three contractions.

See docs/KERNELS.md for the dataflow diagrams and DESIGN.md §10 for the
backward-pass numerics rationale.
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import autotune, ops
from repro.kernels.common import role_stream_salt
from repro.kernels.hbfp_matmul import (gemm_path, hbfp_dgrad_pallas,
                                       hbfp_matmul_pallas, hbfp_wgrad_pallas)
from repro.obs import NULL_RECORDER


class KernelSpec(NamedTuple):
    """Static (hashable) kernel configuration for one matmul call site.

    `m_dgrad`/`m_wgrad` are the per-GEMM-role mantissa widths (DESIGN.md
    §11, `PrecisionPolicy.role_widths`); they default to `mantissa_bits`
    (the fwd width), which is the uniform pre-policy behaviour."""
    mantissa_bits: int
    stochastic: bool
    quantize_w: bool
    fwd: Tuple[int, int, int]     # (bm, bk, bn): M/K-contraction/N tiles
    dgrad: Tuple[int, int, int]   # (bm, bk, bn): M/K/N-contraction tiles
    wgrad: Tuple[int, int, int]   # (bm, bk, bn): M-contraction/K/N tiles
    m_dgrad: int = 0              # 0 ⇒ mantissa_bits
    m_wgrad: int = 0
    block: int = 0                # exponent-block size; 0 ⇒ whole tile


def _pad2(a, mr, mc):
    pr, pc = (-a.shape[0]) % mr, (-a.shape[1]) % mc
    if pr or pc:
        return jnp.pad(a, ((0, pr), (0, pc)))
    return a


def _zero_cotangent(x):
    return np.zeros(np.shape(x), jax.dtypes.float0)


def _tiles(tiles, block: int, M: int, K: int, N: int):
    """The tiles a kernel runs at: the spec's, clipped to the problem and
    rounded up to whole exponent blocks."""
    return autotune.align_tiles(autotune.clip_tiles(tiles, M, K, N), block)


def _fwd_impl(spec: KernelSpec, x2, w, seed):
    M, K = x2.shape
    N = w.shape[1]
    bm, bk, bn = _tiles(spec.fwd, spec.block, M, K, N)
    y = hbfp_matmul_pallas(
        _pad2(x2, bm, bk), _pad2(w, bk, bn), seed,
        mantissa_bits=spec.mantissa_bits, stochastic=spec.stochastic,
        quantize_w=spec.quantize_w, block=spec.block, bm=bm, bk=bk, bn=bn,
        interpret=ops.interpret())
    return y[:M, :N].astype(x2.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _matmul_vjp(spec: KernelSpec, x2, w, seed):
    return _fwd_impl(spec, x2, w, seed)


def _vjp_fwd(spec, x2, w, seed):
    return _fwd_impl(spec, x2, w, seed), (x2, w, seed)


def _role_seed(seed, role: str, m_bits: int, base_bits: int,
               block: int = 0, base_block: int = 0):
    """Seed for one backward GEMM: unsalted at the fwd width + block (the
    kernels' element-index streams replay the forward's draws), xor-salted
    when the role runs at its own width or exponent-block size so it never
    consumes another role's stream (kernels/common.py role_stream_salt;
    pinned by test)."""
    salt = role_stream_salt(role, m_bits, base_bits, block, base_block)
    return seed if not salt else seed ^ jnp.int32(salt)


def _vjp_bwd(spec, res, g):
    x2, w, seed = res
    M, K = x2.shape
    N = w.shape[1]
    m_d = spec.m_dgrad or spec.mantissa_bits
    m_w = spec.m_wgrad or spec.mantissa_bits
    g = g.astype(jnp.float32)
    # dgrad: dx[M,K] = Q(g)·Q(w)^T, contraction over N
    bm, bk, bn = _tiles(spec.dgrad, spec.block, M, K, N)
    dx = hbfp_dgrad_pallas(
        _pad2(g, bm, bn), _pad2(w, bk, bn),
        _role_seed(seed, "dgrad", m_d, spec.mantissa_bits,
                   spec.block, spec.block),
        mantissa_bits=m_d, stochastic=spec.stochastic,
        quantize_w=spec.quantize_w, block=spec.block, bm=bm, bk=bk, bn=bn,
        interpret=ops.interpret())[:M, :K]
    # wgrad: dw[K,N] = Q(x)^T·Q(g), contraction over the token axis M
    bm, bk, bn = _tiles(spec.wgrad, spec.block, M, K, N)
    dw = hbfp_wgrad_pallas(
        _pad2(x2, bm, bk), _pad2(g, bm, bn),
        _role_seed(seed, "wgrad", m_w, spec.mantissa_bits,
                   spec.block, spec.block),
        mantissa_bits=m_w, stochastic=spec.stochastic, block=spec.block,
        bm=bm, bk=bk, bn=bn, interpret=ops.interpret())[:K, :N]
    return dx.astype(x2.dtype), dw.astype(w.dtype), _zero_cotangent(seed)


_matmul_vjp.defvjp(_vjp_fwd, _vjp_bwd)


def seed_from_key(key) -> jax.Array:
    """Fold a JAX PRNG key into the kernels' (1,1) int32 seed. The kernel
    path's xorshift stream is deterministic in this seed but distinct from
    the sim path's threefry draws (DESIGN.md §10)."""
    kd = jax.random.key_data(key).astype(jnp.uint32).reshape(-1)
    return (kd[0] ^ kd[-1]).astype(jnp.int32).reshape(1, 1)


_EVENTS = threading.local()


@contextlib.contextmanager
def gemm_events(recorder):
    """Send `resolve_spec`'s trace-time `kernel/gemm` events to `recorder`
    inside the block (a compile logs each call site's GEMMs once; a
    cached trace logs nothing). `make_step` wraps its step calls in it."""
    prev = getattr(_EVENTS, "recorder", None)
    _EVENTS.recorder = recorder
    try:
        yield recorder
    finally:
        _EVENTS.recorder = prev


def resolve_spec(cfg, M: int, K: int, N: int,
                 dtype: str = "float32",
                 dgrad_cfg=None, wgrad_cfg=None) -> KernelSpec:
    """Build the static KernelSpec for one call site: rounding/width from
    the HBFPConfig, per-GEMM tiles from the autotuner table or the shape
    rule (trace time).
    `dgrad_cfg`/`wgrad_cfg` carry per-role widths (DESIGN.md §11); each
    GEMM's tile lookup is keyed by its own role width, so a "wgrad+2"
    policy consults the m-matched autotune cells (docs/KERNELS.md). The
    config's schedulable block size (`HBFPConfig.act_block`, set by
    `with_block`; DESIGN.md §13) becomes `KernelSpec.block` and keys every
    tile lookup — sub-block scales change the kernel dataflow, so tuned
    tiles don't transfer across block sizes.

    Emits one `kernel/gemm` event per GEMM (fwd, dgrad, wgrad) to the
    recorder `gemm_events` set: op, logical shape, the tiles it runs at
    and its contraction path (`gemm_path`)."""
    m_d = (dgrad_cfg or cfg).mantissa_bits
    m_w = (wgrad_cfg or cfg).mantissa_bits
    block = int(getattr(cfg, "act_block", None) or 0)
    spec = KernelSpec(
        mantissa_bits=cfg.mantissa_bits,
        stochastic=cfg.rounding == "stochastic",
        quantize_w=cfg.requantize_weights,
        fwd=autotune.lookup("matmul_fwd", M, K, N, dtype=dtype,
                            mantissa_bits=cfg.mantissa_bits, block=block),
        dgrad=autotune.lookup("matmul_dgrad", M, K, N, dtype=dtype,
                              mantissa_bits=m_d, block=block),
        wgrad=autotune.lookup("matmul_wgrad", M, K, N, dtype=dtype,
                              mantissa_bits=m_w, block=block),
        m_dgrad=0 if m_d == cfg.mantissa_bits else m_d,
        m_wgrad=0 if m_w == cfg.mantissa_bits else m_w,
        block=block)
    rec = getattr(_EVENTS, "recorder", None) or NULL_RECORDER
    if rec.enabled:
        for op, tiles, m in (("matmul_fwd", spec.fwd, cfg.mantissa_bits),
                             ("matmul_dgrad", spec.dgrad, m_d),
                             ("matmul_wgrad", spec.wgrad, m_w)):
            t = _tiles(tiles, block, M, K, N)
            rec.emit("kernel/gemm", op=op, shape=[M, K, N], tiles=list(t),
                     path=gemm_path(op, mantissa_bits=m,
                                    quantize_w=spec.quantize_w, block=block,
                                    tiles=t),
                     mantissa_bits=m, block=block)
    return spec


def hbfp_matmul_kernel(x: jax.Array, w: jax.Array, cfg,
                       key: Optional[jax.Array] = None, *,
                       dgrad_cfg=None, wgrad_cfg=None) -> jax.Array:
    """BFP matmul y = Q(x)·Q(w) with fused-kernel BFP backward passes.

    Drop-in for `hbfp_ops.hbfp_matmul(x, w, cfg, key)` on the Pallas
    training path (models dispatch here via `Ctx.backend == "pallas"`).
    x: [..., M, K] (leading dims flattened into M); w: [K, N] — batched
    weights stay on the sim path (`models.layers.ctx_matmul` falls back).
    cfg None or ≥ f32-mantissa width ⇒ plain FP matmul, like the sim path.
    `dgrad_cfg`/`wgrad_cfg` (optional) run the backward GEMMs at their own
    mantissa widths (per-role policy widths; rounding/tiling stay cfg's).
    """
    if cfg is None or cfg.mantissa_bits >= 24:
        return jnp.matmul(x, w)
    if w.ndim != 2:
        raise ValueError(f"kernel path needs 2-D w, got {w.shape}")
    K = x.shape[-1]
    N = w.shape[1]
    x2 = x.reshape(-1, K)
    if cfg.rounding == "stochastic":
        if key is None:
            raise ValueError("stochastic rounding requires a key")
        seed = seed_from_key(key)
    else:
        seed = jnp.zeros((1, 1), jnp.int32)
    spec = resolve_spec(cfg, x2.shape[0], K, N, dtype=str(x.dtype),
                        dgrad_cfg=dgrad_cfg, wgrad_cfg=wgrad_cfg)
    y = _matmul_vjp(spec, x2, w, seed)
    return y.reshape(*x.shape[:-1], N)
