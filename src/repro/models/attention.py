"""GQA attention with RoPE/M-RoPE, sliding windows, logit soft-capping,
KV caches (full and ring-buffer), and a q-chunked memory-efficient path.

HBFP: the QK^T and PV contractions are dot products, so they run in BFP when
cfg.quantize_attention (the paper predates attention blocks; DESIGN.md §2
marks this as the natural extension of "all dot products in BFP").
Softmax/masking/rotary stay FP.

Backends (DESIGN.md §10): under Ctx.backend == "pallas", full-causal
training attention (static gate: flash_ok pattern + nearest rounding +
block-divisible S) runs through the fused flash kernel's custom VJP
(`flash_mha`); everything else — windows, softcap, decode caches,
stochastic rounding — stays on the sim path below.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import apply_mrope, apply_rope, ctx_matmul, softcap

NEG_INF = -1e30

_FLASH_BLOCKS = (128, 64, 32, 16, 8)


def _flash_block(S: int):
    """Largest supported flash block dividing S (None ⇒ no flash path)."""
    for b in _FLASH_BLOCKS:
        if S % b == 0:
            return min(b, S)
    return None


class KVCache(NamedTuple):
    k: jax.Array          # [B, Hkv, C, hd] (bf16/f32, or int8 BFP mantissas)
    v: jax.Array          # [B, Hkv, C, hd]
    slot_pos: jax.Array   # [B, C] absolute position per slot (-1 = empty)
    k_exp: Optional[jax.Array] = None   # int8 [B, Hkv, C] (BFP cache mode)
    v_exp: Optional[jax.Array] = None


class PagedKVCache(NamedTuple):
    """Page-pooled KV cache (DESIGN.md §14): a shared pool of fixed-size
    token pages plus a per-lane page table, replacing the dense
    worst-case [B, C, ...] slab. A lane's logical slot `s` lives in pool
    page `page_table[b, s // ps]` at offset `s % ps`; `-1` page-table
    entries are unallocated (reads see empty slots, writes are dropped).
    Pages are allocated on demand by the serving engine (serve/paged_cache)
    and sized to the BFP exponent-block granularity, so a quantized page
    carries its K/V mantissas AND their shared exponents as one unit.

    Shapes below are per-layer (inside the layer scan); the stacked cache
    pytree carries a leading L on every field, page_table included (same
    values every layer — the scan needs uniform leading axes)."""
    k: jax.Array           # [P, Hkv, ps, hd] pool (fp, or int8 mantissas)
    v: jax.Array           # [P, Hkv, ps, hd]
    slot_pos: jax.Array    # [P, ps] absolute position per slot (-1 empty)
    page_table: jax.Array  # [B, NP] int32 pool page ids (-1 unallocated)
    k_exp: Optional[jax.Array] = None   # int8 [P, Hkv, ps] (BFP mode)
    v_exp: Optional[jax.Array] = None


def _acfg(ctx):
    cfg = ctx.cfg
    return cfg if (cfg is not None and cfg.quantize_attention) else None


# --- BFP KV cache (beyond-paper, DESIGN.md §2): K/V vectors stored as 8-bit
# BFP — one exponent per (position, head) vector — halving decode cache
# reads vs bf16 (4x vs f32). Dequantization is exact BFP; attention quality
# matches hbfp8 activations (tests/test_models.py::test_bfp_kv_cache). ---

_KV_M = 8  # mantissa bits


def quantize_kv_vec(x):
    """x: [..., hd] -> (int8 mantissas [..., hd], int8 exponent [...])."""
    from repro.kernels.common import max_exponent, pow2
    amax = jnp.abs(x.astype(jnp.float32)).max(-1, keepdims=True)
    e = max_exponent(amax)
    q = jnp.clip(jnp.rint(x.astype(jnp.float32) / pow2(e - _KV_M + 2)),
                 -127, 127)
    return q.astype(jnp.int8), e.squeeze(-1).astype(jnp.int8)


def dequantize_kv(q, e, dtype):
    from repro.kernels.common import pow2
    scale = pow2(e.astype(jnp.int32) - _KV_M + 2)
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _attend_block(qb, k, v, qpos, kpos, ctx, cap, window):
    """One query block against all kv. qb: [B,Hkv,G,C,hd]; k,v:
    [B,Hkv,S,hd]; qpos: [C] or [B,C]; kpos: [B,S]. Returns [B,Hkv,G,C,hd]."""
    acfg = _acfg(ctx)
    kt = jnp.swapaxes(k, -1, -2)[:, :, None]            # [B,Hkv,1,hd,S]
    scores = ctx_matmul(qb, kt, ctx, "qk", cfg=acfg, w_kind="act")
    scores = scores.astype(jnp.float32)
    scores = softcap(scores, cap)
    if qpos.ndim == 1:
        qp = qpos[None, :, None]                         # [1,C,1]
        kp = kpos[:, None, :]                            # [B,1,S]
    else:
        qp = qpos[:, :, None]
        kp = kpos[:, None, :]
    mask = (kp <= qp) & (kp >= 0)
    if window is not None:
        mask &= kp > qp - window
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(qb.dtype)
    out = ctx_matmul(probs, v[:, :, None], ctx, "pv", cfg=acfg,
                     w_kind="act")
    return out


def flash_mha(q, k, v, ctx):
    """Full-causal training attention on the fused flash kernel
    (custom VJP: forward AND the four backward GEMMs are BFP Pallas
    kernels). q: [B,H,S,hd], k/v: [B,Hkv,S,hd] (GQA groups broadcast; the
    repeat's transpose sums group gradients). Assumes the standard
    contiguous causal layout — position-index masking, no window/softcap
    (attention_layer gates on those statically). Per-role attention widths
    (attn_qk/attn_pv policies) resolve into FlashSpec.m_qk/m_pv, so they
    run on this fast path too (DESIGN.md §11)."""
    from repro.kernels import ops as kops
    from repro.kernels.hbfp_flash_attn import FlashSpec, flash_attention_vjp
    from repro.precision import role_width_for
    B, H, S, hd = q.shape
    Hkv = k.shape[1]
    if Hkv != H:
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
    blk = _flash_block(S)
    m = ctx.cfg.mantissa_bits
    widths = {}
    for role in ("attn_qk", "attn_pv"):
        rw = role_width_for(ctx.roles, role)
        w = rw.apply(ctx.cfg).mantissa_bits if rw is not None else m
        widths[role] = 0 if w == m else w
    spec = FlashSpec(m_bits=m, bq=blk, bk=blk,
                     causal=True, interpret=kops.interpret(),
                     m_qk=widths["attn_qk"], m_pv=widths["attn_pv"])
    out = flash_attention_vjp(spec, q.reshape(B * H, S, hd),
                              k.reshape(B * H, S, hd),
                              v.reshape(B * H, S, hd))
    return out.reshape(B, H, S, hd)


def mha(q, k, v, qpos, kpos, ctx, *, cap=None, window=None,
        q_chunk: Optional[int] = None):
    """q: [B,H,Sq,hd]; k,v: [B,Hkv,Skv,hd]. Causal + optional window.

    q_chunk: if set and Sq > q_chunk, scan over query chunks with a remat'd
    body (memory O(Sq·Skv/n_chunks) instead of O(Sq·Skv))."""
    B, H, Sq, hd = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    scale = 1.0 / (hd ** 0.5)
    qs = (q * scale).reshape(B, Hkv, G, Sq, hd)

    if q_chunk is None or Sq <= q_chunk or Sq % q_chunk != 0:
        out = _attend_block(qs, k, v, qpos, kpos, ctx, cap, window)
        return out.reshape(B, H, Sq, hd)

    nc = Sq // q_chunk
    qs_c = jnp.moveaxis(qs.reshape(B, Hkv, G, nc, q_chunk, hd), 3, 0)
    if qpos.ndim == 1:
        qpos_c = qpos.reshape(nc, q_chunk)
    else:
        qpos_c = jnp.moveaxis(qpos.reshape(B, nc, q_chunk), 1, 0)

    body = jax.checkpoint(
        lambda qb, qp: _attend_block(qb, k, v, qp, kpos, ctx, cap, window))

    def step(_, xs):
        qb, qp = xs
        return None, body(qb, qp)

    _, out = jax.lax.scan(step, None, (qs_c, qpos_c))
    out = jnp.moveaxis(out, 0, 3)                        # [B,Hkv,G,nc,C,hd]
    return out.reshape(B, H, Sq, hd)


# ----------------------------------------------------------------------------
# Cache append (slab and paged): S >= 1 tokens into ring slots pos % C
# ----------------------------------------------------------------------------

def _slab_append(cache: KVCache, k, v, tok_pos, bfp_cache: bool, dtype):
    """Write S tokens into the dense [B, Hkv, C, hd] lane slab and return
    (new_cache, k_dense, v_dense, kpos) for attention. k/v: [B, Hkv, S, hd];
    tok_pos: [B, S]."""
    B = k.shape[0]
    C = cache.k.shape[2]
    slot = tok_pos % C                                   # [B, S]
    bidx = jnp.arange(B)[:, None]                        # [B, 1]
    # advanced-index write: target [B, S, Hkv, *] (batch dims lead)
    kt = jnp.swapaxes(k, 1, 2)                           # [B, S, Hkv, hd]
    vt = jnp.swapaxes(v, 1, 2)
    if bfp_cache:
        kq, ke = quantize_kv_vec(kt)
        vq, ve = quantize_kv_vec(vt)
        nk = cache.k.at[bidx, :, slot].set(kq)
        nv = cache.v.at[bidx, :, slot].set(vq)
        nke = cache.k_exp.at[bidx, :, slot].set(ke)
        nve = cache.v_exp.at[bidx, :, slot].set(ve)
        npos = cache.slot_pos.at[bidx, slot].set(tok_pos)
        new_cache = KVCache(nk, nv, npos, nke, nve)
        kd = dequantize_kv(nk, nke, dtype)
        vd = dequantize_kv(nv, nve, dtype)
    else:
        nk = cache.k.at[bidx, :, slot].set(kt)
        nv = cache.v.at[bidx, :, slot].set(vt)
        npos = cache.slot_pos.at[bidx, slot].set(tok_pos)
        new_cache = KVCache(nk, nv, npos)
        kd, vd = nk, nv
    return new_cache, kd, vd, npos


def _paged_append(cache: PagedKVCache, k, v, tok_pos, bfp_cache: bool,
                  dtype):
    """Paged write + gather (DESIGN.md §14). Writes route through the page
    table (slot s -> pool page page_table[b, s // ps], offset s % ps;
    unallocated entries drop the write); the read gathers exactly this
    lane's pages back into the dense [B, Hkv, C, hd] view the attention
    math expects — bit-identical to the slab path by construction (empty
    pages gather as zeros with slot_pos -1, matching untouched slab
    slots)."""
    B = k.shape[0]
    P, _, ps, _ = cache.k.shape
    NP = cache.page_table.shape[1]
    C = NP * ps
    slot = tok_pos % C                                   # [B, S]
    pidx = slot // ps
    off = slot % ps
    pid = jnp.take_along_axis(cache.page_table, pidx, axis=1)   # [B, S]
    pid = jnp.where(pid < 0, P, pid)       # out-of-range => dropped write
    kt = jnp.swapaxes(k, 1, 2)                           # [B, S, Hkv, hd]
    vt = jnp.swapaxes(v, 1, 2)
    if bfp_cache:
        kt, ke = quantize_kv_vec(kt)
        vt, ve = quantize_kv_vec(vt)
        nke = cache.k_exp.at[pid, :, off].set(ke, mode="drop")
        nve = cache.v_exp.at[pid, :, off].set(ve, mode="drop")
    else:
        nke = nve = None
    nk = cache.k.at[pid, :, off].set(kt, mode="drop")
    nv = cache.v.at[pid, :, off].set(vt, mode="drop")
    nsp = cache.slot_pos.at[pid, off].set(tok_pos, mode="drop")
    new_cache = PagedKVCache(nk, nv, nsp, cache.page_table, nke, nve)

    pt = jnp.where(cache.page_table < 0, P, cache.page_table)   # [B, NP]
    gather = lambda pool, fill: jnp.take(
        pool, pt, axis=0, mode="fill", fill_value=fill)
    kg = gather(nk, 0)                       # [B, NP, Hkv, ps, hd]
    vg = gather(nv, 0)
    Hkv, hd = kg.shape[2], kg.shape[4]
    to_dense = lambda g: g.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, C, hd)
    npos = gather(nsp, -1).reshape(B, C)
    if bfp_cache:
        keg = gather(nke, 0).transpose(0, 2, 1, 3).reshape(B, Hkv, C)
        veg = gather(nve, 0).transpose(0, 2, 1, 3).reshape(B, Hkv, C)
        kd = dequantize_kv(to_dense(kg), keg, dtype)
        vd = dequantize_kv(to_dense(vg), veg, dtype)
    else:
        kd, vd = to_dense(kg), to_dense(vg)
    return new_cache, kd, vd, npos


# ----------------------------------------------------------------------------
# Full attention layer (projections + rotary + cache management)
# ----------------------------------------------------------------------------

def attention_layer(x, p, ctx, *, n_heads, n_kv_heads, head_dim,
                    positions, rope_theta=10000.0, mrope=False,
                    window=None, attn_cap=None, q_chunk=512,
                    cache: Optional[KVCache] = None,
                    return_cache: bool = False,
                    bfp_cache: bool = False,
                    flash_ok: bool = False):
    """x: [B,S,D]. positions: [B,S] (or [3,B,S] for mrope).

    Training/prefill: cache is None; attends causally within x.
    Decode: cache given; S == 1; appends to cache (ring-buffer if the cache
    is smaller than the context) and attends over it.
    flash_ok (static, from the arch): the pattern is full-causal with no
    softcap, so the "pallas" backend may take the fused flash kernel.
    """
    B, S, D = x.shape
    q = ctx_matmul(x, p["attn_wq"], ctx, "wq")
    k = ctx_matmul(x, p["attn_wk"], ctx, "wk")
    v = ctx_matmul(x, p["attn_wv"], ctx, "wv")
    q = q.reshape(B, S, n_heads, head_dim).transpose(0, 2, 1, 3)
    k = k.reshape(B, S, n_kv_heads, head_dim).transpose(0, 2, 1, 3)
    v = v.reshape(B, S, n_kv_heads, head_dim).transpose(0, 2, 1, 3)

    rot = functools.partial(apply_mrope, theta=rope_theta) if mrope \
        else functools.partial(apply_rope, theta=rope_theta)
    q = rot(q, positions)
    k = rot(k, positions)

    tok_pos = positions[0] if mrope else positions       # [B,S] absolute

    if cache is None:
        # fused flash path (DESIGN.md §10): gate on static facts only — the
        # arch's attention pattern (flash_ok), the backend, nearest rounding
        # (the flash kernels are deterministic), and block divisibility.
        # Per-role attention widths (attn_qk/attn_pv) no longer force the
        # sim fallback: FlashSpec carries both contraction widths, so those
        # policies run on the fast path (DESIGN.md §11)
        use_flash = (flash_ok and ctx.backend == "pallas"
                     and ctx.cfg is not None and ctx.cfg.quantize_attention
                     and ctx.cfg.rounding == "nearest"
                     and _flash_block(S) is not None)
        qpos = tok_pos if tok_pos.ndim == 2 else tok_pos
        if use_flash:
            out = flash_mha(q, k, v, ctx)
        else:
            out = mha(q, k, v, qpos, tok_pos, ctx, cap=attn_cap,
                      window=window, q_chunk=q_chunk)
        new_cache = None
        if return_cache:
            if bfp_cache:
                kq, ke = quantize_kv_vec(k)
                vq, ve = quantize_kv_vec(v)
                new_cache = KVCache(kq, vq, tok_pos, ke, ve)
            else:
                new_cache = KVCache(k=k, v=v, slot_pos=tok_pos)
    else:
        # decode / chunked prefill: write the S incoming tokens into their
        # ring slots (pos % C), then attend the whole query block over the
        # cache — causality within the chunk falls out of the kp <= qp
        # mask, so S == 1 (decode) and S > 1 (prefill chunks) share one
        # path. PagedKVCache routes the same writes/reads through the
        # page-table indirection (DESIGN.md §14).
        if isinstance(cache, PagedKVCache):
            new_cache, kd, vd, npos = _paged_append(cache, k, v, tok_pos,
                                                    bfp_cache, x.dtype)
        else:
            new_cache, kd, vd, npos = _slab_append(cache, k, v, tok_pos,
                                                   bfp_cache, x.dtype)
        out = mha(q, kd, vd, tok_pos, npos, ctx, cap=attn_cap, window=window,
                  q_chunk=None)

    out = out.transpose(0, 2, 1, 3).reshape(B, S, n_heads * head_dim)
    out = ctx_matmul(out, p["attn_wo"], ctx, "wo")
    return out, new_cache


def init_attention(key, d_model, n_heads, n_kv_heads, head_dim,
                   dtype=jnp.float32, out_scale=None):
    ks = jax.random.split(key, 4)
    s = d_model ** -0.5
    so = (n_heads * head_dim) ** -0.5 if out_scale is None else out_scale
    return {
        "attn_wq": jax.random.normal(ks[0], (d_model, n_heads * head_dim),
                                     dtype) * s,
        "attn_wk": jax.random.normal(ks[1], (d_model, n_kv_heads * head_dim),
                                     dtype) * s,
        "attn_wv": jax.random.normal(ks[2], (d_model, n_kv_heads * head_dim),
                                     dtype) * s,
        "attn_wo": jax.random.normal(ks[3], (n_heads * head_dim, d_model),
                                     dtype) * so,
    }
