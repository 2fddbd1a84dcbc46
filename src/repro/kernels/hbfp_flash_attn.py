"""Pallas TPU kernel: fused HBFP flash attention (beyond-paper).

The paper fuses FP→BFP conversion into the MatMul unit so "conversions are
infrequent and account for an insignificant fraction of area" (§2). The same
insight applied to attention: QK^T and PV are dot products ⇒ BFP; softmax is
range-sensitive ⇒ FP32 — all inside one VMEM-resident flash kernel, so the
[S×S] score matrix never touches HBM (the memory-roofline fix identified in
EXPERIMENTS.md §Roofline for the prefill cells).

Per (q-block, k-block) step:
  1. quantize q rows / k rows to 8-bit BFP (exponent per vector — matching
     models/attention.py's w_kind="act" semantics),
  2. int8 MXU dot → int32 → rescale by δq·δk,
  3. online-softmax update (m, l running max/sum, f32 — the "FP side"),
  4. quantize probs per row, PV int8 dot, rescale, accumulate f32.

Causal masking by absolute position; fully-masked k-blocks short-circuit.
Oracle: ref.hbfp_flash_attn_ref (bit-exact, shared quantize_block).

Training path (docs/KERNELS.md, DESIGN.md §10): `flash_attention_vjp` is a
jax.custom_vjp whose backward is two further fused Pallas kernels (the
standard two-pass flash backward — one producing dQ, one producing dK/dV),
each recomputing the probabilities from the forward's saved logsumexp and
running its dot products in BFP:

    s  = Q(q·α)·Q(k)^T        (idempotent with the forward's quantization)
    p  = exp(s − lse)          FP (range-sensitive)
    dp = Q(do)·Q(v)^T          int8 path (row scales factor per output)
    ds = p ∘ (dp − D)          FP
    dv += Q(p)^T ⊙ Q(do)       FP accumulate (scales ride the q contraction)
    dk += Q(ds)^T ⊙ Q(q·α)     FP accumulate
    dq += Q(ds) ⊙ Q(k) · α     FP accumulate

where D = rowsum(do ∘ o) is precomputed outside (elementwise, FP side).
Oracle: ref.hbfp_flash_attn_vjp_ref (bit-exact, same blocking and
accumulation order).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import quantize_block

NEG_INF = -1e30


def _qdot(a, b, m_bits):
    """BFP dot: int8 path for m<=8, exact-f32 otherwise. a:[M,K] b:[K,N]."""
    if m_bits <= 8:
        return jax.lax.dot_general(
            a.astype(jnp.int8), b.astype(jnp.int8), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.float32)
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest,
                  m_qk, m_pv, bq, bk, hd, n_k, scale, causal, with_lse):
    if with_lse:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        lse_ref = None
        m_ref, l_ref, acc_ref = rest
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qb = pl.program_id(1)
    run = (not causal) or (kb * bk <= qb * bq + bq - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale        # [bq, hd]
        k = k_ref[0].astype(jnp.float32)                # [bk, hd]
        v = v_ref[0].astype(jnp.float32)                # [bk, hd]
        # BFP: one exponent per q-row / k-row over hd (act semantics);
        # QK-side operands at m_qk, PV-side at m_pv (per-role widths,
        # DESIGN.md §11 — attn_qk/attn_pv policies run on this fast path)
        qq, dq = quantize_block(q, m_qk, jnp.abs(q).max(1, keepdims=True),
                                stochastic=False)
        kq, dk = quantize_block(k, m_qk, jnp.abs(k).max(1, keepdims=True),
                                stochastic=False)
        s = _qdot(qq, kq.T, m_qk) * (dq * dk.T)         # [bq, bk] f32
        if causal:
            qpos = qb * bq + jax.lax.broadcasted_iota(jnp.int32,
                                                      (bq, bk), 0)
            kpos = kb * bk + jax.lax.broadcasted_iota(jnp.int32,
                                                      (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        # online softmax (FP side)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                          # [bq, bk]
        l_ref[...] = l_ref[...] * alpha + p.sum(1, keepdims=True)
        # PV in BFP: probs per row over bk, v per column over bk
        pq, dp = quantize_block(p, m_pv, jnp.abs(p).max(1, keepdims=True),
                                stochastic=False)
        vq, dv = quantize_block(v, m_pv,
                                jnp.abs(v).max(0, keepdims=True),
                                stochastic=False)
        pv = _qdot(pq, vq, m_pv) * (dp * dv)            # [bq, hd]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(kb == n_k - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))


@functools.partial(jax.jit, static_argnames=("m_bits", "m_qk", "m_pv",
                                             "bq", "bk", "causal",
                                             "with_lse", "interpret"))
def hbfp_flash_attention(q, k, v, *, m_bits: int = 8, m_qk: int = 0,
                         m_pv: int = 0, bq: int = 128,
                         bk: int = 128, causal: bool = True,
                         with_lse: bool = False, interpret: bool = False):
    """q,k,v: [BH, S, hd] (flattened batch×heads). Returns [BH, S, hd], or
    (out, lse [BH, S] f32) when with_lse — the per-row logsumexp of the
    scaled BFP scores, saved by the custom VJP for the backward pass.
    m_qk/m_pv (0 ⇒ m_bits) run the QK^T and PV contractions at their own
    mantissa widths (per-role attention policies, DESIGN.md §11)."""
    BH, S, hd = q.shape
    bq, bk = min(bq, S), min(bk, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    n_k = S // bk
    scale = 1.0 / (hd ** 0.5)
    kernel = functools.partial(_flash_kernel, m_qk=m_qk or m_bits,
                               m_pv=m_pv or m_bits, bq=bq, bk=bk,
                               hd=hd, n_k=n_k, scale=scale, causal=causal,
                               with_lse=with_lse)
    out_shape = jax.ShapeDtypeStruct((BH, S, hd), q.dtype)
    out_spec = pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0))
    if with_lse:
        # per-row vectors travel as [BH, S, 1] columns: a (1, bq, 1) block
        # meets Mosaic's (8, 128) tiling rule, a (1, bq) row block does not
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((BH, S, 1), jnp.float32)]
        out_spec = [out_spec,
                    pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))]
    out = pl.pallas_call(
        kernel,
        grid=(BH, S // bq, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=interpret,
        name="hbfp_flash_fwd",
    )(q, k, v)
    if with_lse:
        return out[0], out[1][..., 0]
    return out


# ----------------------------------------------------------------------------
# Backward kernels (two-pass flash backward, all dot products BFP)
# ----------------------------------------------------------------------------

def _recompute_p(q, k, lse, qb, kb, m_qk, bq, bk, scale, causal):
    """Shared by both backward kernels: re-quantize q·α and k exactly as the
    forward did (idempotent, at the QK width) and rebuild p = exp(s − lse)."""
    qq, dq = quantize_block(q, m_qk, jnp.abs(q).max(1, keepdims=True),
                            stochastic=False)
    kq, dk = quantize_block(k, m_qk, jnp.abs(k).max(1, keepdims=True),
                            stochastic=False)
    s = _qdot(qq, kq.T, m_qk) * (dq * dk.T)             # [bq, bk]
    if causal:
        qpos = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)
    p = jnp.exp(s - lse)                                # [bq, bk]
    return p, (qq, dq), (kq, dk)


def _bfp_rows(x, m_bits):
    """Quantize per row (one exponent per training input over the block's
    feature axis) and dequantize — the FP-accumulate operand form used when
    the per-row scales ride the contraction axis."""
    q, d = quantize_block(x, m_bits, jnp.abs(x).max(1, keepdims=True),
                          stochastic=False)
    return q, d


def _dsoft(p, do_q, do_d, v, delta, m_pv):
    """dp = Q(do)·Q(v)^T (int8 path — row scales factor per output cell;
    PV-side operands at the PV width), then ds = p ∘ (dp − D)."""
    vq, dv = _bfp_rows(v, m_pv)
    dp = _qdot(do_q, vq.T, m_pv) * (do_d * dv.T)        # [bq, bk]
    return p * (dp - delta)


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, acc_ref, *, m_qk, m_pv, bq, bk, hd, n_k, scale,
                     causal):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qb = pl.program_id(1)
    run = (not causal) or (kb * bk <= qb * bq + bq - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]
        p, _, (kq, dk) = _recompute_p(q, k, lse, qb, kb, m_qk, bq, bk,
                                      scale, causal)
        do_q, do_d = _bfp_rows(do, m_pv)
        ds = _dsoft(p, do_q, do_d, v, delta, m_pv)
        # dq += Q(ds)·k̂ · α — k̂'s per-row scales ride the contraction;
        # ds is a QK-GEMM gradient operand ⇒ QK width
        ds_q, ds_d = _bfp_rows(ds, m_qk)
        acc_ref[...] += jax.lax.dot_general(
            ds_q * ds_d, kq * dk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(kb == n_k - 1)
    def _done():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dk_acc, dv_acc, *, m_qk, m_pv, bq, bk,
                      hd, n_q, scale, causal):
    qb = pl.program_id(2)

    @pl.when(qb == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    kb = pl.program_id(1)
    run = (not causal) or (qb * bq + bq - 1 >= kb * bk)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]
        p, (qq, dq), _ = _recompute_p(q, k, lse, qb, kb, m_qk, bq, bk,
                                      scale, causal)
        do_q, do_d = _bfp_rows(do, m_pv)
        # dv += Q(p)^T·Q(do) — p re-quantized per q-row exactly like the
        # forward's PV operand (PV width); scales ride the q contraction
        # ⇒ f32 path
        p_q, p_d = _bfp_rows(p, m_pv)
        dv_acc[...] += jax.lax.dot_general(
            p_q * p_d, do_q * do_d, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = _dsoft(p, do_q, do_d, v, delta, m_pv)
        # dk += Q(ds)^T·q̂ (q̂ carries the α scaling from the forward);
        # QK-GEMM gradient operand ⇒ QK width
        ds_q, ds_d = _bfp_rows(ds, m_qk)
        dk_acc[...] += jax.lax.dot_general(
            ds_q * ds_d, qq * dq, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qb == n_q - 1)
    def _done():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("m_bits", "m_qk", "m_pv",
                                             "bq", "bk", "causal",
                                             "interpret"))
def hbfp_flash_attention_bwd(q, k, v, o, lse, do, *, m_bits: int = 8,
                             m_qk: int = 0, m_pv: int = 0,
                             bq: int = 128, bk: int = 128,
                             causal: bool = True, interpret: bool = False):
    """Fused BFP flash-attention backward: returns (dq, dk, dv), each
    [BH, S, hd]. Two pallas_calls: dq iterates k-blocks per q-block; dk/dv
    iterate q-blocks per k-block."""
    BH, S, hd = q.shape
    bq, bk = min(bq, S), min(bk, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    scale = 1.0 / (hd ** 0.5)
    # lse/delta as [BH, S, 1] columns (see hbfp_flash_attention)
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(
        -1, keepdims=True)
    lse = lse[..., None]
    specs = [
        pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),   # q
        pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),   # k
        pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),   # v
        pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),   # do
        pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),    # lse
        pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),    # delta
    ]
    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, m_qk=m_qk or m_bits,
                          m_pv=m_pv or m_bits, bq=bq, bk=bk,
                          hd=hd, n_k=S // bk, scale=scale, causal=causal),
        grid=(BH, S // bq, S // bk),
        in_specs=specs,
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=interpret,
        name="hbfp_flash_dq",
    )(q, k, v, do, lse, delta)
    # dk/dv grid swaps the roles: (b, k-block, q-block), q innermost
    specs_kv = [
        pl.BlockSpec((1, bq, hd), lambda b, j, i: (b, i, 0)),   # q
        pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),   # k
        pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),   # v
        pl.BlockSpec((1, bq, hd), lambda b, j, i: (b, i, 0)),   # do
        pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),    # lse
        pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),    # delta
    ]
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, m_qk=m_qk or m_bits,
                          m_pv=m_pv or m_bits, bq=bq, bk=bk,
                          hd=hd, n_q=S // bq, scale=scale, causal=causal),
        grid=(BH, S // bk, S // bq),
        in_specs=specs_kv,
        out_specs=[pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),
                   pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
                   jax.ShapeDtypeStruct((BH, S, hd), q.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd), jnp.float32)],
        interpret=interpret,
        name="hbfp_flash_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ----------------------------------------------------------------------------
# custom VJP: the training entry point
# ----------------------------------------------------------------------------

class FlashSpec(NamedTuple):
    """Static flash-attention kernel configuration. `m_qk`/`m_pv` (0 ⇒
    m_bits) are the per-role widths of the two attention contractions —
    attn_qk/attn_pv policies run on the fused path instead of falling back
    to the sim oracle (DESIGN.md §11)."""
    m_bits: int
    bq: int
    bk: int
    causal: bool
    interpret: bool
    m_qk: int = 0
    m_pv: int = 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def flash_attention_vjp(spec: FlashSpec, q, k, v):
    return hbfp_flash_attention(q, k, v, m_bits=spec.m_bits,
                                m_qk=spec.m_qk, m_pv=spec.m_pv, bq=spec.bq,
                                bk=spec.bk, causal=spec.causal,
                                interpret=spec.interpret)


def _flash_fwd(spec, q, k, v):
    o, lse = hbfp_flash_attention(q, k, v, m_bits=spec.m_bits,
                                  m_qk=spec.m_qk, m_pv=spec.m_pv, bq=spec.bq,
                                  bk=spec.bk, causal=spec.causal,
                                  with_lse=True, interpret=spec.interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(spec, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = hbfp_flash_attention_bwd(
        q, k, v, o, lse, do, m_bits=spec.m_bits, m_qk=spec.m_qk,
        m_pv=spec.m_pv, bq=spec.bq, bk=spec.bk,
        causal=spec.causal, interpret=spec.interpret)
    return dq, dk, dv


flash_attention_vjp.defvjp(_flash_fwd, _flash_bwd)
