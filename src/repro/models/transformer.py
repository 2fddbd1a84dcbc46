"""Composable transformer-family model: dense / MoE / hybrid / xLSTM stacks
with scan-over-layers, KV/SSM caches, and HBFP threaded through every dot
product.

Entry points:
  init_params(key, arch)                       -> params pytree
  forward(params, batch, arch, ctx)            -> (logits, aux)
  loss_fn(params, batch, arch, ctx)            -> (loss, metrics)
  prefill(params, batch, arch, ctx)            -> (logits_last, cache)
  decode_step(params, batch, cache, arch, ctx) -> (logits, cache)

`batch` keys: "tokens" [B,S] (or [B,S,K] codebooks) | "embeds" [B,S,D];
"positions" [B,S] (or [3,B,S] for mrope); "labels" like tokens.
Caches are stacked per-layer pytrees (leading dim L) updated inside the
layer scan.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.attention import (KVCache, PagedKVCache, attention_layer,
                                    init_attention)
from repro.models.layers import (Ctx, ctx_matmul, gelu_ffn, rms_norm,
                                 softcap, swiglu_ffn)

BIG_WINDOW = 1 << 30


# ----------------------------------------------------------------------------
# init
# ----------------------------------------------------------------------------

def _init_layer(key, arch: ArchConfig, dtype):
    ks = jax.random.split(key, 8)
    D, F = arch.d_model, arch.d_ff
    p: Dict[str, Any] = {}
    if arch.xlstm:
        p.update(xlstm_mod.init_mlstm(ks[0], D, arch.n_heads, dtype))
        p.update(xlstm_mod.init_slstm(ks[1], D, arch.n_heads, dtype))
        return p
    p["ln1_norm_scale"] = jnp.zeros((D,), jnp.float32) \
        if arch.zero_centered_norm else jnp.ones((D,), jnp.float32)
    p["ln2_norm_scale"] = jnp.array(p["ln1_norm_scale"])
    if arch.post_norms:
        p["post1_norm_scale"] = jnp.array(p["ln1_norm_scale"])
        p["post2_norm_scale"] = jnp.array(p["ln1_norm_scale"])
    p.update(init_attention(ks[2], D, arch.n_heads, arch.n_kv_heads,
                            arch.hd, dtype))
    if arch.ssm:
        p["ssm_branch_norm_scale"] = jnp.ones((D,), jnp.float32)
        p["attn_branch_norm_scale"] = jnp.ones((D,), jnp.float32)
        p.update(ssm_mod.init_ssm(ks[3], D, arch.d_inner, arch.n_heads,
                                  arch.ssm_state, dtype))
    if arch.n_experts:
        p.update(moe_mod.init_moe(
            ks[4], D, F, arch.n_experts, dtype,
            dense_residual=arch.moe_dense_residual,
            dense_ff=F, shared_expert=arch.shared_expert))
    else:
        s = D ** -0.5
        p["ffn_wg"] = jax.random.normal(ks[5], (D, F), dtype) * s
        p["ffn_wi"] = jax.random.normal(ks[6], (D, F), dtype) * s
        p["ffn_wo"] = jax.random.normal(ks[7], (F, D), dtype) * (F ** -0.5)
    return p


def init_params(key, arch: ArchConfig):
    dtype = jnp.dtype(arch.dtype)
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, arch.n_layers)
    layers = jax.vmap(lambda k: _init_layer(k, arch, dtype))(layer_keys)
    params = {"layers": layers,
              "final_norm_scale": jnp.zeros((arch.d_model,), jnp.float32)
              if arch.zero_centered_norm
              else jnp.ones((arch.d_model,), jnp.float32)}
    if arch.input_kind == "tokens":
        params["embed_table"] = (jax.random.normal(
            k_emb, (arch.vocab_size, arch.d_model), dtype) * 0.02)
    if arch.n_codebooks > 1:
        params["head_w"] = jax.random.normal(
            k_head, (arch.n_codebooks, arch.d_model, arch.vocab_size),
            dtype) * (arch.d_model ** -0.5)
    else:
        params["head_w"] = jax.random.normal(
            k_head, (arch.d_model, arch.vocab_size), dtype) \
            * (arch.d_model ** -0.5)
    return params


# ----------------------------------------------------------------------------
# layer body
# ----------------------------------------------------------------------------

def _layer_windows(arch: ArchConfig, n_layers: int):
    """Per-layer attention window (int32 [L]); BIG_WINDOW = full causal."""
    idx = jnp.arange(n_layers)
    if arch.attn_pattern == "local_global":
        # gemma2: even layers local (sliding window), odd layers global
        return jnp.where(idx % 2 == 0, arch.window, BIG_WINDOW)
    if arch.attn_pattern == "sliding":
        return jnp.full((n_layers,), arch.window, jnp.int32)
    return jnp.full((n_layers,), BIG_WINDOW, jnp.int32)


def _attn_ffn_block(x, lp, ctx, arch: ArchConfig, positions, window,
                    cache, want_cache: bool, std_pos: bool = False):
    """Standard pre-norm block; gemma2 adds post-norms; hymba adds the
    parallel mamba branch. Returns (x, new_cache, aux)."""
    with jax.named_scope("model.attn"):
        x, new_cache = _attn_half(x, lp, ctx, arch, positions, window,
                                  cache, want_cache, std_pos)
    with jax.named_scope("model.ffn"):
        x, aux = _ffn_half(x, lp, ctx, arch)
    return x, new_cache, aux


def _attn_half(x, lp, ctx, arch: ArchConfig, positions, window, cache,
               want_cache: bool, std_pos: bool):
    """Norm, attention (and hymba's SSM branch), residual: (x, new_cache)."""
    h = rms_norm(x, lp["ln1_norm_scale"], arch.norm_eps,
                 arch.zero_centered_norm)
    a, new_kv = attention_layer(
        h, lp, ctx, n_heads=arch.n_heads, n_kv_heads=arch.n_kv_heads,
        head_dim=arch.hd, positions=positions, rope_theta=arch.rope_theta,
        mrope=arch.mrope, window=window, attn_cap=arch.attn_softcap,
        q_chunk=arch.q_chunk,
        cache=None if cache is None else cache["kv"],
        return_cache=want_cache, bfp_cache=arch.bfp_kv_cache,
        # flash masks by block index, so it additionally requires the
        # standard synthesized arange positions (std_pos) — explicit
        # batch positions (packed sequences, offsets) stay on mha, which
        # masks by the actual position values
        flash_ok=(arch.attn_pattern == "global"
                  and arch.attn_softcap is None and std_pos))
    new_cache = {} if (want_cache or cache is not None) else None
    if new_cache is not None:
        new_cache["kv"] = new_kv
    if arch.ssm:
        s, new_ssm = ssm_mod.ssm_branch(
            h, lp, ctx, n_heads=arch.n_heads, d_state=arch.ssm_state,
            chunk=arch.ssm_chunk, unroll=arch.ssm_unroll,
            state=None if cache is None else cache["ssm"])
        # hymba: mean of per-branch normalized outputs
        a = 0.5 * (rms_norm(a, lp["attn_branch_norm_scale"], arch.norm_eps)
                   + rms_norm(s, lp["ssm_branch_norm_scale"], arch.norm_eps))
        if new_cache is not None:
            new_cache["ssm"] = new_ssm
    if arch.post_norms:
        a = rms_norm(a, lp["post1_norm_scale"], arch.norm_eps,
                     arch.zero_centered_norm)
    return x + arch.residual_scale * a, new_cache


def _ffn_half(x, lp, ctx, arch: ArchConfig):
    """Norm, FFN or MoE, residual: (x, MoE aux loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, lp["ln2_norm_scale"], arch.norm_eps,
                 arch.zero_centered_norm)
    if arch.n_experts:
        f, aux = moe_mod.moe_ffn(
            h, lp, ctx, n_experts=arch.n_experts, top_k=arch.top_k,
            capacity_factor=arch.capacity_factor, n_groups=arch.moe_groups,
            dense_residual=arch.moe_dense_residual,
            shared_expert=arch.shared_expert)
    elif arch.ffn_act == "geglu":
        f = gelu_ffn(h, lp, ctx)
    else:
        f = swiglu_ffn(h, lp, ctx)
    if arch.post_norms:
        f = rms_norm(f, lp["post2_norm_scale"], arch.norm_eps,
                     arch.zero_centered_norm)
    return x + arch.residual_scale * f, aux


def _xlstm_block(x, lp, ctx, arch: ArchConfig, is_slstm, cache,
                 want_cache: bool):
    """xLSTM layer. Both branches are evaluated and `is_slstm` (a scanned
    per-layer flag) selects one — keeps the layer scan homogeneous; the
    inactive branch's state is carried through unchanged."""
    B = x.shape[0]
    m_st = cache["mlstm"] if cache is not None else None
    s_st = cache["slstm"] if cache is not None else None
    y_m, new_m = xlstm_mod.mlstm_block(x, lp, ctx, n_heads=arch.n_heads,
                                       chunk=arch.ssm_chunk, state=m_st,
                                       unroll=arch.ssm_unroll)
    y_s, new_s = xlstm_mod.slstm_block(x, lp, ctx, n_heads=arch.n_heads,
                                       state=s_st)
    y = jnp.where(is_slstm, y_s, y_m)
    new_cache = None
    if want_cache or cache is not None:
        m0 = m_st if m_st is not None else \
            xlstm_mod.mlstm_state_init(B, arch.n_heads, arch.d_model)
        s0 = s_st if s_st is not None else \
            xlstm_mod.slstm_state_init(B, arch.d_model)
        new_cache = {
            "mlstm": jax.tree.map(
                lambda keep, new: jnp.where(is_slstm, keep, new), m0, new_m),
            "slstm": jax.tree.map(
                lambda keep, new: jnp.where(is_slstm, new, keep), s0, new_s),
        }
    return y, new_cache, jnp.zeros((), jnp.float32)


# ----------------------------------------------------------------------------
# stack
# ----------------------------------------------------------------------------

def _embed_in(params, batch, arch: ArchConfig, ctx):
    with jax.named_scope("model.embed"):
        return _embed(params, batch, arch)


def _embed(params, batch, arch: ArchConfig):
    if arch.input_kind == "embeddings":
        x = batch["embeds"].astype(jnp.dtype(arch.dtype))
    else:
        tok = batch["tokens"]
        if arch.n_codebooks > 1 and tok.ndim == 3:
            # musicgen: sum of codebook embeddings (delay-pattern stub)
            emb = params["embed_table"]
            x = emb[tok].sum(axis=2)
        else:
            x = params["embed_table"][tok]
    x = x * arch.emb_scale
    B, S = x.shape[:2]
    if "positions" in batch:
        positions = batch["positions"]
    else:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))
        if arch.mrope:
            positions = jnp.broadcast_to(positions[None], (3, B, S))
    return x, positions


def _run_stack(params, x, positions, arch: ArchConfig, ctx,
               cache=None, want_cache: bool = False,
               std_pos: bool = False):
    L = arch.n_layers
    windows = _layer_windows(arch, L)
    layer_ids = jnp.arange(L)
    is_slstm = (layer_ids % arch.slstm_every == arch.slstm_every - 1) \
        if arch.xlstm and arch.slstm_every else jnp.zeros((L,), bool)

    def body(x, xs):
        lp, win, lid, sl, cache_l = xs
        lctx = ctx.fold(lid)
        if ctx.act_constraint is not None:
            # sequence-parallel residual stream (Megatron-SP): the remat'd
            # per-layer saved input is the CONSTRAINED (seq-sharded) copy
            x = ctx.act_constraint(x)
        if arch.xlstm:
            y, new_cache, aux = _xlstm_block(x, lp, lctx, arch, sl, cache_l,
                                             want_cache)
        else:
            y, new_cache, aux = _attn_ffn_block(x, lp, lctx, arch, positions,
                                                win, cache_l, want_cache,
                                                std_pos)
        return y, (new_cache, aux)

    body_fn = jax.checkpoint(body) if arch.remat else body

    if not arch.scan_layers:
        # unrolled path (roofline extraction: per-layer costs visible in HLO)
        caches, auxs = [], []
        for i in range(L):
            xs_i = jax.tree.map(lambda t: t[i],
                                (params["layers"], windows, layer_ids,
                                 is_slstm, cache))
            x, (nc, aux) = body_fn(x, xs_i)
            caches.append(nc)
            auxs.append(aux)
        new_cache = None if caches[0] is None else \
            jax.tree.map(lambda *ls: jnp.stack(ls), *caches)
        return x, new_cache, jnp.stack(auxs).sum()

    xs = (params["layers"], windows, layer_ids, is_slstm, cache)
    x, (new_cache, aux) = jax.lax.scan(body_fn, x, xs)
    return x, new_cache, aux.sum()


def _head_logits(params, x, arch: ArchConfig, ctx):
    """LM head on [..., D] hidden states → f32 logits [..., (K,) V]."""
    hcfg = ctx.cfg if (ctx.cfg and ctx.cfg.quantize_lm_head) else None
    if arch.n_codebooks > 1:
        logits = jnp.stack(
            [ctx_matmul(x, params["head_w"][k], ctx, f"head{k}", cfg=hcfg)
             for k in range(arch.n_codebooks)], axis=-2)
    else:
        logits = ctx_matmul(x, params["head_w"], ctx, "head", cfg=hcfg)
    logits = logits / arch.logit_divisor
    return softcap(logits.astype(jnp.float32), arch.final_softcap)


def _logits(params, x, arch: ArchConfig, ctx):
    with jax.named_scope("model.head"):
        x = rms_norm(x, params["final_norm_scale"], arch.norm_eps,
                     arch.zero_centered_norm)
        return _head_logits(params, x, arch, ctx)


# ----------------------------------------------------------------------------
# public API
# ----------------------------------------------------------------------------

def _std_positions(batch) -> bool:
    """True when attention may mask by block index (the flash path):
    positions are either synthesized (absent from the batch) or a CONCRETE
    host-side array equal to the standard contiguous arange — explicit
    positions with standard values are just the default layout spelled
    out. Traced positions can't be inspected at trace time, and packed /
    offset layouts have non-arange values; both stay on the sim path,
    which masks by the actual position values."""
    if "positions" not in batch:
        return True
    pos = batch["positions"]
    if isinstance(pos, jax.core.Tracer):
        return False
    p = np.asarray(pos)
    if p.ndim not in (2, 3):  # [B, S] or mrope [3, B, S]
        return False
    return bool((p == np.arange(p.shape[-1], dtype=p.dtype)).all())


def forward(params, batch, arch: ArchConfig, ctx: Ctx):
    x, positions = _embed_in(params, batch, arch, ctx)
    x, _, aux = _run_stack(params, x, positions, arch, ctx,
                           std_pos=_std_positions(batch))
    return _logits(params, x, arch, ctx), aux


def loss_fn(params, batch, arch: ArchConfig, ctx: Ctx,
            aux_weight: float = 0.01):
    """Next-token CE. The LM head + softmax-CE is computed in token chunks
    (scan, remat'd) so the f32 [tokens, vocab] logits never materialize in
    full — per-device temp drops from O(B·S·V) to O(chunk·V)."""
    x, positions = _embed_in(params, batch, arch, ctx)
    act_stats = None
    if ctx.act_tap and ctx.cfg is not None:
        # numerics observatory (DESIGN.md §9): fidelity of quantizing the
        # residual stream at stack entry/exit. Measurement only (the
        # forward pass itself is untouched; aux outputs are not
        # differentiated). Per-layer activation taps would need aux
        # threading through the layer scan — same non-goal as per-layer
        # activation schedules (§8).
        from repro.numerics.stats import quantize_with_stats
        from repro.core.bfp import act_tile_shape

        def tap(t):
            return quantize_with_stats(
                t, ctx.cfg.mantissa_bits,
                act_tile_shape(t.ndim, ctx.cfg.act_block))[1]

        act_stats = {"embed_out": tap(x)}
    x, _, aux = _run_stack(params, x, positions, arch, ctx,
                           std_pos=_std_positions(batch))
    if act_stats is not None:
        act_stats["final_hidden"] = tap(x)
    labels = batch["labels"]
    with jax.named_scope("model.head"):
        tot = _head_nll(params, x, labels, arch, ctx)
    T = x.shape[0] * x.shape[1]
    denom = T * (labels.shape[2] if labels.ndim == 3 else 1)
    nll = tot / denom
    loss = nll + aux_weight * aux
    metrics = {"nll": nll, "aux": aux, "loss": loss}
    if act_stats is not None:
        metrics["act_stats"] = act_stats
    return loss, metrics


def _head_nll(params, x, labels, arch: ArchConfig, ctx):
    """Final norm, LM head and summed next-token CE over [B, S, D] hidden
    states, in token chunks of arch.loss_chunk where they divide."""
    x = rms_norm(x, params["final_norm_scale"], arch.norm_eps,
                 arch.zero_centered_norm)
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    lt = labels.reshape(B * S, *labels.shape[2:])

    def ce(xc, lc):
        logits = _head_logits(params, xc, arch, ctx)       # [t, (K,) V]
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lc[..., None], axis=-1).squeeze(-1)
        return (lse - ll).sum()

    T = B * S
    loss_chunk = arch.loss_chunk
    if loss_chunk and T > loss_chunk and T % loss_chunk == 0:
        nc = T // loss_chunk
        xc = xt.reshape(nc, loss_chunk, D)
        lc = lt.reshape(nc, loss_chunk, *lt.shape[1:])
        body = jax.checkpoint(lambda c, xs: (c + ce(*xs), None))
        tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xc, lc))
        return tot
    return ce(xt, lt)


def make_cache(params, arch: ArchConfig, batch_size: int, ctx_len: int):
    """Allocate an empty stacked decode cache."""
    L, B = arch.n_layers, batch_size
    dtype = jnp.dtype(arch.dtype)
    if arch.xlstm:
        m = xlstm_mod.mlstm_state_init(B, arch.n_heads, arch.d_model)
        s = xlstm_mod.slstm_state_init(B, arch.d_model)
        stack = lambda t: jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (L,) + a.shape) + 0, t)
        return {"mlstm": stack(m), "slstm": stack(s)}
    C = lane_capacity(arch, ctx_len)
    if arch.bfp_kv_cache:
        kv = KVCache(
            k=jnp.zeros((L, B, arch.n_kv_heads, C, arch.hd), jnp.int8),
            v=jnp.zeros((L, B, arch.n_kv_heads, C, arch.hd), jnp.int8),
            slot_pos=jnp.full((L, B, C), -1, jnp.int32),
            k_exp=jnp.zeros((L, B, arch.n_kv_heads, C), jnp.int8),
            v_exp=jnp.zeros((L, B, arch.n_kv_heads, C), jnp.int8))
    else:
        kv = KVCache(
            k=jnp.zeros((L, B, arch.n_kv_heads, C, arch.hd), dtype),
            v=jnp.zeros((L, B, arch.n_kv_heads, C, arch.hd), dtype),
            slot_pos=jnp.full((L, B, C), -1, jnp.int32))
    cache = {"kv": kv}
    if arch.ssm:
        h = ssm_mod.ssm_state_init(B, arch.n_heads, arch.d_inner,
                                   arch.ssm_state)
        cache["ssm"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (L,) + a.shape) + 0, h)
    return cache


def lane_capacity(arch: ArchConfig, ctx_len: int) -> int:
    """Per-lane KV slot count the decode cache actually allocates: the
    sliding-window archs ring over min(window, ctx_len); everything else
    keeps the full ctx_len."""
    if arch.attn_pattern == "sliding" and arch.window is not None:
        return min(arch.window, ctx_len)
    return ctx_len


def make_paged_cache(params, arch: ArchConfig, batch_size: int,
                     ctx_len: int, n_pages: int, page_size: int):
    """Allocate an empty page-pooled decode cache (DESIGN.md §14): the KV
    leaves become one shared [L, P, Hkv, ps, hd] pool + a [L, B, NP] page
    table (NP = lane capacity / ps), instead of per-lane worst-case slabs.
    SSM states stay dense per-lane (they are O(1) in sequence length —
    nothing to page). xLSTM archs have no KV cache to page."""
    if arch.xlstm:
        raise ValueError("xlstm archs have no KV cache to page")
    C = lane_capacity(arch, ctx_len)
    if C % page_size:
        raise ValueError(f"page_size {page_size} must divide the lane "
                         f"capacity {C}")
    L, P, ps = arch.n_layers, n_pages, page_size
    NP = C // ps
    dtype = jnp.dtype(arch.dtype)
    pt = jnp.full((L, batch_size, NP), -1, jnp.int32)
    if arch.bfp_kv_cache:
        kv = PagedKVCache(
            k=jnp.zeros((L, P, arch.n_kv_heads, ps, arch.hd), jnp.int8),
            v=jnp.zeros((L, P, arch.n_kv_heads, ps, arch.hd), jnp.int8),
            slot_pos=jnp.full((L, P, ps), -1, jnp.int32),
            page_table=pt,
            k_exp=jnp.zeros((L, P, arch.n_kv_heads, ps), jnp.int8),
            v_exp=jnp.zeros((L, P, arch.n_kv_heads, ps), jnp.int8))
    else:
        kv = PagedKVCache(
            k=jnp.zeros((L, P, arch.n_kv_heads, ps, arch.hd), dtype),
            v=jnp.zeros((L, P, arch.n_kv_heads, ps, arch.hd), dtype),
            slot_pos=jnp.full((L, P, ps), -1, jnp.int32),
            page_table=pt)
    cache = {"kv": kv}
    if arch.ssm:
        h = ssm_mod.ssm_state_init(batch_size, arch.n_heads, arch.d_inner,
                                   arch.ssm_state)
        cache["ssm"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (L,) + a.shape) + 0, h)
    return cache


def prefill(params, batch, arch: ArchConfig, ctx: Ctx):
    """Forward over the prompt; returns (last-token logits, cache)."""
    x, positions = _embed_in(params, batch, arch, ctx)
    x, cache, _ = _run_stack(params, x, positions, arch, ctx,
                             want_cache=True,
                             std_pos=_std_positions(batch))
    logits = _logits(params, x[:, -1:], arch, ctx)
    return logits, cache


def decode_step(params, batch, cache, arch: ArchConfig, ctx: Ctx):
    """One token step. batch: tokens [B,1] / embeds [B,1,D] + positions."""
    x, positions = _embed_in(params, batch, arch, ctx)
    x, cache, _ = _run_stack(params, x, positions, arch, ctx, cache=cache)
    logits = _logits(params, x, arch, ctx)
    return logits, cache
