"""Plain float32 reference of a dense pre-norm decoder (Llama layout, with
MiniCPM's embedding, residual and logit scales where the configuration
gives them), its loss, and AdamW.

It follows the published description and imports nothing of the program
under test. Every contraction runs at `Precision.HIGHEST`, so a TPU keeps
float32 accuracy. Attention runs in blocks of queries, each recomputed in
the backward pass, so the reference fits one chip at the timed sizes.

Layer: h = x + r * Attn(RMSNorm(x)); y = h + r * SwiGLU(RMSNorm(h)), with
r = scale_depth / sqrt(scale_depth_layers) (1 without), RoPE on the
split halves of each head, grouped KV heads, causal softmax. Embedding
times scale_emb; logits divided by hidden_size / dim_model_base.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_HI = lax.Precision.HIGHEST
F32 = jnp.float32
Q_BLOCK = 512


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HI, preferred_element_type=F32)


def scales(cfg: dict):
    """(embedding, residual, logit-divisor) scales of the configuration."""
    emb = float(cfg.get("scale_emb", 1.0))
    res = 1.0
    if cfg.get("scale_depth") is not None:
        res = cfg["scale_depth"] / cfg["scale_depth_layers"] ** 0.5
    div = 1.0
    if cfg.get("dim_model_base") is not None:
        div = cfg["hidden_size"] / cfg["dim_model_base"]
    return emb, res, div


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: [B, S, H, hd] at positions 0..S-1; rotates the split halves."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv          # [S, hd/2]
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v):
    """Causal softmax attention, q: [B, S, H, hd], k/v: [B, S, H, hd]."""
    B, S, H, hd = q.shape
    qb = min(Q_BLOCK, S)
    nb = S // qb
    kpos = jnp.arange(S)

    @jax.checkpoint
    def block(i):
        qi = lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        s = _mm("bqhd,bkhd->bhqk", qi, k) / hd ** 0.5
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("bhqk,bkhd->bqhd", p, v)

    out = lax.map(block, jnp.arange(nb))                 # [nb, B, qb, H, hd]
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, hd)


def _layer(cfg, x, lp):
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    _, r, _ = scales(cfg)
    B, S, _ = x.shape
    h = _rms(x, lp["ln1_norm_scale"], eps)
    q = _mm("bsd,de->bse", h, lp["attn_wq"]).reshape(B, S, H, hd)
    k = _mm("bsd,de->bse", h, lp["attn_wk"]).reshape(B, S, Hkv, hd)
    v = _mm("bsd,de->bse", h, lp["attn_wv"]).reshape(B, S, Hkv, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    a = _attention(q, k, v).reshape(B, S, H * hd)
    x = x + r * _mm("bse,ed->bsd", a, lp["attn_wo"])
    h = _rms(x, lp["ln2_norm_scale"], eps)
    g = _mm("bsd,df->bsf", h, lp["ffn_wg"])
    u = _mm("bsd,df->bsf", h, lp["ffn_wi"])
    return x + r * _mm("bsf,fd->bsd", jax.nn.silu(g) * u, lp["ffn_wo"])


def logits(cfg: dict, params, tokens):
    """f32 logits [B, S, V] of token ids [B, S]."""
    emb, _, div = scales(cfg)
    p = jax.tree.map(lambda a: a.astype(F32), params)
    x = p["embed_table"][tokens] * emb
    body = jax.checkpoint(lambda x, lp: (_layer(cfg, x, lp), None))
    x, _ = lax.scan(body, x, p["layers"])
    x = _rms(x, p["final_norm_scale"], cfg["rms_norm_eps"])
    return _mm("bsd,dv->bsv", x, p["head_w"]) / div


def loss(cfg: dict, params, batch):
    """Mean next-token cross-entropy over every row and position."""
    lg = logits(cfg, params, batch["tokens"])
    lse = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(lse - ll)


def leaf_norms(tree):
    """Euclidean norm of every leaf, as a tree of f32 scalars."""
    return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(F32)))), tree)


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3, 4))
def adamw_step(cfg_items, hp_items, params, mu, nu, batch, t):
    """One f32 AdamW step with global-norm clipping. Returns (params, mu,
    nu, loss, clipped-gradient leaf norms, raw-gradient leaf norms)."""
    cfg, hp = dict(cfg_items), dict(hp_items)
    val, g = jax.value_and_grad(lambda p: loss(cfg, p, batch))(params)
    raw = leaf_norms(g)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    g = jax.tree.map(
        lambda x: x * jnp.minimum(1.0, hp["grad_clip"] / (gnorm + 1e-9)), g)
    b1, b2, eps = hp["b1"], hp["b2"], hp["eps"]
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree.map(lambda n, x: b2 * n + (1 - b2) * x * x, nu, g)
    tf = t.astype(F32)
    bc1, bc2 = 1 - b1 ** tf, 1 - b2 ** tf

    def upd(p, m, n):
        u = (m / bc1) / (jnp.sqrt(n / bc2) + eps)
        if p.ndim >= 2:  # decoupled decay on every leaf stored as a matrix
            u = u + hp["weight_decay"] * p
        return p - hp["lr"] * u

    params = jax.tree.map(upd, params, mu, nu)
    return params, mu, nu, val, leaf_norms(g), raw


def train_readings(cfg: dict, hp: dict, params0, batches):
    """Run len(batches) reference steps from params0 (a tree of host
    arrays, any float dtype). Returns the losses, the first step's clipped
    and raw gradient leaf norms, and the leaf norms of the parameters'
    change over all steps (taken on the host, in float64)."""
    items, hp_items = cfg_items(cfg), tuple(sorted(hp.items()))
    params = jax.tree.map(lambda a: jnp.asarray(a, F32), params0)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, g1, raw1 = [], None, None
    for i, b in enumerate(batches):
        params, mu, nu, val, gn, raw = adamw_step(
            items, hp_items, params, mu, nu, b, jnp.int32(i + 1))
        losses.append(float(val))
        if i == 0:
            g1, raw1 = jax.device_get(gn), jax.device_get(raw)
    del mu, nu
    change = jax.tree.map(
        lambda new, old: float(np.linalg.norm(
            (np.asarray(new, np.float64) - np.asarray(old, np.float64))
            .ravel())), jax.device_get(params), params0)
    return {"losses": losses, "grad1": g1, "grad1_raw": raw1,
            "change": change}


def cfg_items(cfg: dict):
    """The configuration's scalar entries as a hashable, static tuple."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))

