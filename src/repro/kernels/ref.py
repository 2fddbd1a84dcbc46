"""Pure-jnp oracles mirroring the Pallas kernels' exact semantics.

Both oracles share `kernels.common.quantize_block` with the kernel bodies, so
nearest-rounding results are bit-exact and stochastic-rounding results use the
identical counter-based xorshift stream — tests assert exact equality.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import (GROUP, STREAM_G, STREAM_W, STREAM_X,
                                  dequantize_rows, quantize_block,
                                  row_group_amax, slice_width, small_groups,
                                  tile_group_amax)


def bfp_quantize_ref(x, seed, *, mantissa_bits=8, tile_r=128, tile_c=128,
                     stochastic=False, block_r=256, block_c=512,
                     with_stats=False):
    """Oracle for bfp_quantize_pallas: same zero-padding of non-divisible
    shapes, same block fitting, same fused stat outputs. Returns
    (mantissa, exponent) or (mantissa, exponent, clip_count per tile,
    exp_min per block, exp_max per block)."""
    from repro.kernels.bfp_quantize import _fit_block
    R, C = x.shape
    tr, tc = min(tile_r, R), min(tile_c, C)
    Rp, Cp = -(-R // tr) * tr, -(-C // tc) * tc
    if (Rp, Cp) != (R, C):
        x = jnp.pad(x, ((0, Rp - R), (0, Cp - C)))
    g = x.astype(jnp.float32).reshape(Rp // tr, tr, Cp // tc, tc)
    amax = jnp.abs(g).max(axis=(1, 3), keepdims=True)
    idx = None
    if stochastic:
        rows = jax.lax.broadcasted_iota(jnp.int32, (Rp, Cp), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (Rp, Cp), 1)
        idx = (rows * Cp + cols).reshape(g.shape)
    q, delta, clipped = quantize_block(
        g, mantissa_bits, amax, stochastic=stochastic,
        seed=jnp.asarray(seed).reshape(-1)[0], idx=idx, with_clip=True)
    mdt = jnp.int8 if mantissa_bits <= 8 else jnp.int16
    dbits = jax.lax.bitcast_convert_type(delta, jnp.int32)
    e = ((dbits >> 23) & 0xFF) - 127 + (mantissa_bits - 2)
    et = e[:, 0, :, 0]
    mant = q.reshape(Rp, Cp).astype(mdt)[:R, :C]
    if not with_stats:
        return mant, et.astype(jnp.int8)
    # per-block exponent min/max with the kernel's fitted block grid
    btr = _fit_block(Rp // tr, max(min(block_r, Rp) // tr, 1))
    btc = _fit_block(Cp // tc, max(min(block_c, Cp) // tc, 1))
    eb = et.reshape(Rp // tr // btr, btr, Cp // tc // btc, btc)
    return (mant, et.astype(jnp.int8),
            clipped.sum(axis=(1, 3)).astype(jnp.int32),
            eb.min(axis=(1, 3)).astype(jnp.int32),
            eb.max(axis=(1, 3)).astype(jnp.int32))


def hbfp_matmul_ref(x, w, seed=None, *, mantissa_bits=8, stochastic=False,
                    quantize_w=True, block=0, bm=128, bk=128, bn=128,
                    out_dtype=jnp.float32):
    """Oracle for hbfp_matmul_pallas: per-(row, exponent group) activation
    exponents, per-sub-tile weight exponents (128 x 128 at block=0, clamped
    to the tile), f32 accumulation over the groups of K in ascending
    order. quantize_w=False mirrors the kernel's pre-narrowed-weight path
    (raw w, f32 contraction). block>0 sets the group: per-(row, block)
    for x and (block, block) sub-tiles for w (DESIGN.md §13)."""
    M, K = x.shape
    _, N = w.shape
    bk_, bn_ = min(bk, K), min(bn, N)
    s = slice_width(block, bk_)
    x_sub = small_groups(block, s)
    w_sub = small_groups(block, s, bn_)
    cw = bn_ if w_sub or not quantize_w else min(block or GROUP, bn_)
    seed_v = jnp.zeros((), jnp.int32) if seed is None \
        else jnp.asarray(seed).reshape(-1)[0]
    xf = x.astype(jnp.float32)
    wf = w.astype(jnp.float32)

    acc = jnp.zeros((M, N), jnp.float32)
    for kk in range(K // s):
        xs = xf[:, kk * s:(kk + 1) * s]                          # [M, s]
        ax = row_group_amax(xs, block)
        idx_x = None
        if stochastic:
            r = jax.lax.broadcasted_iota(jnp.int32, (M, s), 0)
            c = jax.lax.broadcasted_iota(jnp.int32, (M, s), 1)
            idx_x = r * K + (kk * s + c) + jnp.int32(STREAM_X)
        qx, dx = quantize_block(xs, mantissa_bits, ax, stochastic=stochastic,
                                seed=seed_v, idx=idx_x)
        for jj in range(N // cw):
            ws = wf[kk * s:(kk + 1) * s, jj * cw:(jj + 1) * cw]
            cols = slice(jj * cw, (jj + 1) * cw)
            if not quantize_w:
                if x_sub:
                    part = _dot(qx * dx, ws, (1,), (0,))
                    acc = acc.at[:, cols].add(part)
                else:
                    part = _dot(qx, ws, (1,), (0,))
                    acc = acc.at[:, cols].add(part * dx)
                continue
            aw = tile_group_amax(ws, block if w_sub else 0)
            idx_w = None
            if stochastic:
                rw = jax.lax.broadcasted_iota(jnp.int32, (s, cw), 0)
                cl = jax.lax.broadcasted_iota(jnp.int32, (s, cw), 1)
                idx_w = ((kk * s + rw) * N + (jj * cw + cl)
                         + jnp.int32(STREAM_W))
            qw, dw = quantize_block(ws, mantissa_bits, aw,
                                    stochastic=stochastic, seed=seed_v,
                                    idx=idx_w)
            if w_sub:
                acc = acc.at[:, cols].add(
                    _dot(qx * dx, qw * dw, (1,), (0,)))
                continue
            part = _dot(qx, qw, (1,), (0,), int8=mantissa_bits <= 8)
            acc = acc.at[:, cols].add(part * (dx * dw))
    return acc.astype(out_dtype)


def hbfp_dgrad_ref(g, w, seed=None, *, mantissa_bits=8, stochastic=False,
                   quantize_w=True, block=0, bm=128, bk=128, bn=128,
                   out_dtype=jnp.float32):
    """Oracle for hbfp_dgrad_pallas: dx[M,K] = Q(g)·Q(w)^T, gradient rows
    quantized per (row, exponent group of N), weight sub-tiles as in the
    forward, f32 accumulation over the groups of N in kernel order. block>0
    sets the group exactly like hbfp_matmul_ref."""
    M, N = g.shape
    K, _ = w.shape
    bk_, bn_ = min(bk, K), min(bn, N)
    s = slice_width(block, bn_)
    g_sub = small_groups(block, s)
    w_sub = small_groups(block, s, bk_)
    rw_ = bk_ if w_sub or not quantize_w else min(block or GROUP, bk_)
    seed_v = jnp.zeros((), jnp.int32) if seed is None \
        else jnp.asarray(seed).reshape(-1)[0]
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)

    acc = jnp.zeros((M, K), jnp.float32)
    for nn in range(N // s):
        gs = gf[:, nn * s:(nn + 1) * s]                          # [M, s]
        ag = row_group_amax(gs, block)
        idx_g = None
        if stochastic:
            r = jax.lax.broadcasted_iota(jnp.int32, (M, s), 0)
            c = jax.lax.broadcasted_iota(jnp.int32, (M, s), 1)
            idx_g = r * N + (nn * s + c) + jnp.int32(STREAM_G)
        qg, dg = quantize_block(gs, mantissa_bits, ag, stochastic=stochastic,
                                seed=seed_v, idx=idx_g)
        for jj in range(K // rw_):
            ws = wf[jj * rw_:(jj + 1) * rw_, nn * s:(nn + 1) * s]
            cols = slice(jj * rw_, (jj + 1) * rw_)
            if not quantize_w:
                if g_sub:
                    part = _dot(qg * dg, ws, (1,), (1,))
                    acc = acc.at[:, cols].add(part)
                else:
                    part = _dot(qg, ws, (1,), (1,))
                    acc = acc.at[:, cols].add(part * dg)
                continue
            aw = tile_group_amax(ws, block if w_sub else 0)
            idx_w = None
            if stochastic:
                rw = jax.lax.broadcasted_iota(jnp.int32, (rw_, s), 0)
                cw = jax.lax.broadcasted_iota(jnp.int32, (rw_, s), 1)
                idx_w = ((jj * rw_ + rw) * N + (nn * s + cw)
                         + jnp.int32(STREAM_W))
            qw, dw = quantize_block(ws, mantissa_bits, aw,
                                    stochastic=stochastic, seed=seed_v,
                                    idx=idx_w)
            if w_sub:
                acc = acc.at[:, cols].add(
                    _dot(qg * dg, qw * dw, (1,), (1,)))
                continue
            part = _dot(qg, qw, (1,), (1,), int8=mantissa_bits <= 8)
            acc = acc.at[:, cols].add(part * (dg * dw))
    return acc.astype(out_dtype)


def hbfp_wgrad_ref(x, g, seed=None, *, mantissa_bits=8, stochastic=False,
                   block=0, bm=128, bk=128, bn=128, out_dtype=jnp.float32):
    """Oracle for hbfp_wgrad_pallas: dw[K,N] = Q(x)^T·Q(g). Both operands
    take per-(row, exponent group) activation exponents (x over K on the
    forward's stream, g over N on the dgrad stream); per-token scales
    ride the contraction, so dequantized f32 outer products accumulate in
    kernel order over M blocks."""
    M, K = x.shape
    _, N = g.shape
    bm_, bk_, bn_ = min(bm, M), min(bk, K), min(bn, N)
    seed_v = jnp.zeros((), jnp.int32) if seed is None \
        else jnp.asarray(seed).reshape(-1)[0]
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)

    def idx(mm, c0, rows, cols, stride, stream):
        if not stochastic:
            return None
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
        return (mm * rows + r) * stride + (c0 + c) + jnp.int32(stream)

    acc = jnp.zeros((K, N), jnp.float32)
    for mm in range(M // bm_):
        xs = xf[mm * bm_:(mm + 1) * bm_]                         # [bm, K]
        gs = gf[mm * bm_:(mm + 1) * bm_]                         # [bm, N]
        for ii in range(K // bk_):
            xh = dequantize_rows(
                xs[:, ii * bk_:(ii + 1) * bk_], mantissa_bits, block,
                stochastic=stochastic, seed=seed_v,
                idx=idx(mm, ii * bk_, bm_, bk_, K, STREAM_X))
            for jj in range(N // bn_):
                gh = dequantize_rows(
                    gs[:, jj * bn_:(jj + 1) * bn_], mantissa_bits, block,
                    stochastic=stochastic, seed=seed_v,
                    idx=idx(mm, jj * bn_, bm_, bn_, N, STREAM_G))
                acc = acc.at[ii * bk_:(ii + 1) * bk_,
                             jj * bn_:(jj + 1) * bn_].add(
                    _dot(xh, gh, (0,), (0,)))
    return acc.astype(out_dtype)


def _dot(a, b, ca, cb, int8=False):
    """The kernels' MXU contraction: int8 mantissas with an exact int32
    accumulate, or f32."""
    if int8:
        return jax.lax.dot_general(
            a.astype(jnp.int8), b.astype(jnp.int8), ((ca, cb), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.float32)
    return jax.lax.dot_general(a, b, ((ca, cb), ((), ())),
                               preferred_element_type=jnp.float32)


def hbfp_flash_attn_ref(q, k, v, *, m_bits=8, m_qk=0, m_pv=0, bq=128,
                        bk=128, causal=True, with_lse=False):
    """Oracle for hbfp_flash_attention: same per-block BFP quantization,
    same online-softmax order of operations (bit-exact in f32).
    with_lse=True additionally returns the per-row logsumexp [BH, S].
    m_qk/m_pv (0 ⇒ m_bits) are the per-role contraction widths."""
    BH, S, hd = q.shape
    m_qk, m_pv = m_qk or m_bits, m_pv or m_bits
    bq_, bk_ = min(bq, S), min(bk, S)
    scale = 1.0 / (hd ** 0.5)
    out = jnp.zeros_like(q, jnp.float32)
    lse_out = jnp.zeros((BH, S), jnp.float32)
    for b in range(BH):
        for i in range(S // bq_):
            qs = q[b, i * bq_:(i + 1) * bq_].astype(jnp.float32) * scale
            qq, dq = quantize_block(qs, m_qk,
                                    jnp.abs(qs).max(1, keepdims=True),
                                    stochastic=False)
            m = jnp.full((bq_, 1), -1e30, jnp.float32)
            l = jnp.zeros((bq_, 1), jnp.float32)
            acc = jnp.zeros((bq_, hd), jnp.float32)
            for j in range(S // bk_):
                if causal and j * bk_ > i * bq_ + bq_ - 1:
                    continue
                ks = k[b, j * bk_:(j + 1) * bk_].astype(jnp.float32)
                vs = v[b, j * bk_:(j + 1) * bk_].astype(jnp.float32)
                kq, dk = quantize_block(ks, m_qk,
                                        jnp.abs(ks).max(1, keepdims=True),
                                        stochastic=False)
                if m_qk <= 8:
                    s = jax.lax.dot_general(
                        qq.astype(jnp.int8), kq.T.astype(jnp.int8),
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.int32
                    ).astype(jnp.float32) * (dq * dk.T)
                else:
                    s = (qq @ kq.T) * (dq * dk.T)
                if causal:
                    qpos = i * bq_ + jnp.arange(bq_)[:, None]
                    kpos = j * bk_ + jnp.arange(bk_)[None, :]
                    s = jnp.where(kpos <= qpos, s, -1e30)
                m_new = jnp.maximum(m, s.max(1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)
                l = l * alpha + p.sum(1, keepdims=True)
                pq, dp = quantize_block(p, m_pv,
                                        jnp.abs(p).max(1, keepdims=True),
                                        stochastic=False)
                vq, dv = quantize_block(vs, m_pv,
                                        jnp.abs(vs).max(0, keepdims=True),
                                        stochastic=False)
                if m_pv <= 8:
                    pv = jax.lax.dot_general(
                        pq.astype(jnp.int8), vq.astype(jnp.int8),
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.int32
                    ).astype(jnp.float32) * (dp * dv)
                else:
                    pv = (pq @ vq) * (dp * dv)
                acc = acc * alpha + pv
                m = m_new
            out = out.at[b, i * bq_:(i + 1) * bq_].set(
                acc / jnp.maximum(l, 1e-30))
            lse_out = lse_out.at[b, i * bq_:(i + 1) * bq_].set(
                (m + jnp.log(jnp.maximum(l, 1e-30)))[:, 0])
    if with_lse:
        return out.astype(q.dtype), lse_out
    return out.astype(q.dtype)


def hbfp_flash_attn_vjp_ref(q, k, v, do, *, m_bits=8, m_qk=0, m_pv=0,
                            bq=128, bk=128, causal=True):
    """Oracle for hbfp_flash_attention_bwd: same BFP quantization of every
    backward GEMM operand, same block order (dq accumulates over k-blocks
    per q-block; dk/dv over q-blocks per k-block). Returns (dq, dk, dv).
    m_qk/m_pv (0 ⇒ m_bits): QK-side operands (q, k, ds) at the QK width,
    PV-side operands (p, v, do) at the PV width."""
    BH, S, hd = q.shape
    m_qk, m_pv = m_qk or m_bits, m_pv or m_bits
    bq_, bk_ = min(bq, S), min(bk, S)
    scale = 1.0 / (hd ** 0.5)
    out, lse = hbfp_flash_attn_ref(q, k, v, m_bits=m_bits, m_qk=m_qk,
                                   m_pv=m_pv, bq=bq_, bk=bk_,
                                   causal=causal, with_lse=True)
    dof = do.astype(jnp.float32)
    delta = (dof * out.astype(jnp.float32)).sum(-1)      # [BH, S]

    def rows(x, m):
        return quantize_block(x, m, jnp.abs(x).max(1, keepdims=True),
                              stochastic=False)

    def recompute(b, i, j):
        qs = q[b, i * bq_:(i + 1) * bq_].astype(jnp.float32) * scale
        ks = k[b, j * bk_:(j + 1) * bk_].astype(jnp.float32)
        qq, dqv = rows(qs, m_qk)
        kq, dkv = rows(ks, m_qk)
        if m_qk <= 8:
            s = jax.lax.dot_general(
                qq.astype(jnp.int8), kq.T.astype(jnp.int8),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32
            ).astype(jnp.float32) * (dqv * dkv.T)
        else:
            s = (qq @ kq.T) * (dqv * dkv.T)
        if causal:
            qpos = i * bq_ + jnp.arange(bq_)[:, None]
            kpos = j * bk_ + jnp.arange(bk_)[None, :]
            s = jnp.where(kpos <= qpos, s, -1e30)
        p = jnp.exp(s - lse[b, i * bq_:(i + 1) * bq_][:, None])
        return p, (qq, dqv), (kq, dkv)

    def dsoft(b, i, j, p, do_q, do_d):
        vs = v[b, j * bk_:(j + 1) * bk_].astype(jnp.float32)
        vq, dv_ = rows(vs, m_pv)
        if m_pv <= 8:
            dp = jax.lax.dot_general(
                do_q.astype(jnp.int8), vq.T.astype(jnp.int8),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32
            ).astype(jnp.float32) * (do_d * dv_.T)
        else:
            dp = (do_q @ vq.T) * (do_d * dv_.T)
        return p * (dp - delta[b, i * bq_:(i + 1) * bq_][:, None])

    dq = jnp.zeros((BH, S, hd), jnp.float32)
    dk = jnp.zeros((BH, S, hd), jnp.float32)
    dv = jnp.zeros((BH, S, hd), jnp.float32)
    for b in range(BH):
        for i in range(S // bq_):
            acc = jnp.zeros((bq_, hd), jnp.float32)
            do_q, do_d = rows(dof[b, i * bq_:(i + 1) * bq_], m_pv)
            for j in range(S // bk_):
                if causal and j * bk_ > i * bq_ + bq_ - 1:
                    continue
                p, _, (kq, dkv) = recompute(b, i, j)
                ds = dsoft(b, i, j, p, do_q, do_d)
                ds_q, ds_d = rows(ds, m_qk)
                acc = acc + ((ds_q * ds_d) @ (kq * dkv)) * scale
            dq = dq.at[b, i * bq_:(i + 1) * bq_].set(acc)
        for j in range(S // bk_):
            acc_k = jnp.zeros((bk_, hd), jnp.float32)
            acc_v = jnp.zeros((bk_, hd), jnp.float32)
            for i in range(S // bq_):
                if causal and j * bk_ > i * bq_ + bq_ - 1:
                    continue
                p, (qq, dqv), _ = recompute(b, i, j)
                do_q, do_d = rows(dof[b, i * bq_:(i + 1) * bq_], m_pv)
                p_q, p_d = rows(p, m_pv)
                acc_v = acc_v + jax.lax.dot_general(
                    p_q * p_d, do_q * do_d, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                ds = dsoft(b, i, j, p, do_q, do_d)
                ds_q, ds_d = rows(ds, m_qk)
                acc_k = acc_k + jax.lax.dot_general(
                    ds_q * ds_d, qq * dqv, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            dk = dk.at[b, j * bk_:(j + 1) * bk_].set(acc_k)
            dv = dv.at[b, j * bk_:(j + 1) * bk_].set(acc_v)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))
