"""Dense pre-norm decoder (Llama layout, with MiniCPM's embedding,
residual and logit scales where the configuration gives them): global
causal attention with grouped KV heads and RoPE, SwiGLU FFN, RMSNorm.

Layer: h = x + r * Attn(RMSNorm(x)); y = h + r * SwiGLU(RMSNorm(h)), with
r = scale_depth / sqrt(scale_depth_layers) (1 without), RoPE on the
split halves of each head, grouped KV heads, causal softmax. Embedding
times scale_emb; logits divided by hidden_size / dim_model_base.

The reference follows the published description and imports nothing of
the program under test. Attention runs in blocks of queries, each
recomputed in the backward pass, so the reference fits one chip at the
timed sizes.

It provides what every structure module must (the contract, in
structures/__init__.py): program_arch, make_params, logits, loss,
weight_gemms, attention_fwd_ops, attention_shape and trainable_params.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from harness import SetupError
from structures import F32, mm

Q_BLOCK = 512


# -- the program's architecture -----------------------------------------------

# what the program's ArchConfig must say for this module's reference to be
# the program's model
STRUCTURE = {"family": "dense", "attn_pattern": "global",
             "ffn_act": "swiglu", "attn_softcap": None,
             "final_softcap": None, "zero_centered_norm": False,
             "post_norms": False, "n_experts": 0, "ssm": False,
             "xlstm": False, "mrope": False, "input_kind": "tokens",
             "n_codebooks": 1, "bfp_kv_cache": False}


def program_arch(cfg: dict):
    """The program's ArchConfig for this configuration file: the program's
    own registered architecture (`program_arch` in the file) gives the
    structure; every number the file states replaces the registered one."""
    from repro.configs import get_arch
    base = get_arch(cfg["program_arch"])
    emb, res, div = scales(cfg)
    arch = dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"],
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        dtype=cfg["torch_dtype"], emb_scale=emb, residual_scale=res,
        logit_divisor=div)
    check(arch, cfg["program_arch"])
    return arch


def check(arch, name: str):
    """SetupError unless `arch` is the dense decoder the reference
    implements."""
    for k, v in STRUCTURE.items():
        if getattr(arch, k) != v:
            raise SetupError(f"{name}: {k}={getattr(arch, k)!r} is not the "
                             f"dense decoder the reference implements "
                             f"({v!r})")


# -- weights ------------------------------------------------------------------

def make_params(cfg: dict, key, dtype=jnp.bfloat16):
    """Random weights at the configuration's sizes: normal, scaled by
    fan-in (0.02 for the embedding), norm scales at one."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
    ks = jax.random.split(key, 9)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    layers = {
        "ln1_norm_scale": jnp.ones((L, D), jnp.float32),
        "ln2_norm_scale": jnp.ones((L, D), jnp.float32),
        "attn_wq": normal(ks[0], (L, D, H * hd), D),
        "attn_wk": normal(ks[1], (L, D, Hkv * hd), D),
        "attn_wv": normal(ks[2], (L, D, Hkv * hd), D),
        "attn_wo": normal(ks[3], (L, H * hd, D), H * hd),
        "ffn_wg": normal(ks[4], (L, D, F), D),
        "ffn_wi": normal(ks[5], (L, D, F), D),
        "ffn_wo": normal(ks[6], (L, F, D), F),
    }
    return {"layers": layers,
            "final_norm_scale": jnp.ones((D,), jnp.float32),
            "embed_table": normal(ks[7], (V, D), 2500.0),  # std 0.02
            "head_w": normal(ks[8], (D, V), D)}


# -- the f32 reference --------------------------------------------------------

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
        s = mm("bqhd,bkhd->bhqk", qi, k) / hd ** 0.5
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("bhqk,bkhd->bqhd", p, v)

    out = lax.map(block, jnp.arange(nb))                 # [nb, B, qb, H, hd]
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, hd)


def _layer(cfg, x, lp):
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    _, r, _ = scales(cfg)
    B, S, _ = x.shape
    h = _rms(x, lp["ln1_norm_scale"], eps)
    q = mm("bsd,de->bse", h, lp["attn_wq"]).reshape(B, S, H, hd)
    k = mm("bsd,de->bse", h, lp["attn_wk"]).reshape(B, S, Hkv, hd)
    v = mm("bsd,de->bse", h, lp["attn_wv"]).reshape(B, S, Hkv, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    a = _attention(q, k, v).reshape(B, S, H * hd)
    x = x + r * mm("bse,ed->bsd", a, lp["attn_wo"])
    h = _rms(x, lp["ln2_norm_scale"], eps)
    g = mm("bsd,df->bsf", h, lp["ffn_wg"])
    u = mm("bsd,df->bsf", h, lp["ffn_wi"])
    return x + r * mm("bsf,fd->bsd", jax.nn.silu(g) * u, lp["ffn_wo"])


def logits(cfg: dict, params, tokens):
    """f32 logits [B, S, V] of token ids [B, S]."""
    emb, _, div = scales(cfg)
    p = jax.tree.map(lambda a: a.astype(F32), params)
    x = p["embed_table"][tokens] * emb
    body = jax.checkpoint(lambda x, lp: (_layer(cfg, x, lp), None))
    x, _ = lax.scan(body, x, p["layers"])
    x = _rms(x, p["final_norm_scale"], cfg["rms_norm_eps"])
    return mm("bsd,dv->bsv", x, p["head_w"]) / div


def loss(cfg: dict, params, batch):
    """Mean next-token cross-entropy over every row and position."""
    lg = logits(cfg, params, batch["tokens"])
    lse = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(lse - ll)


# -- work ---------------------------------------------------------------------

def weight_gemms(cfg: dict, tokens: int):
    """Each layer's seven projections, then the LM head."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    per_layer = [("wq", D, H * hd), ("wk", D, Hkv * hd),
                 ("wv", D, Hkv * hd), ("wo", H * hd, D),
                 ("ffn_g", D, F), ("ffn_i", D, F), ("ffn_o", F, D)]
    out = []
    for layer in range(cfg["num_hidden_layers"]):
        out += [(f"{layer}.{n}", tokens, k, n_) for n, k, n_ in per_layer]
    out.append(("head", tokens, D, cfg["vocab_size"]))
    return out


def attention_fwd_ops(cfg: dict, batch: int, seq: int) -> int:
    """Global causal attention: per head and sequence, QK^T and PV over
    the S(S+1)/2 visible pairs."""
    L, H, _, hd = attention_shape(cfg)
    return L * batch * H * 2 * 2 * hd * seq * (seq + 1) // 2


def attention_shape(cfg: dict):
    return (cfg["num_hidden_layers"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def trainable_params(cfg: dict):
    """The weight GEMMs and the LM head, the embedding table, and the
    RMSNorm scales (two a layer and the final one)."""
    D = cfg["hidden_size"]
    matrices = (sum(k * n for _, _, k, n in weight_gemms(cfg, 1))
                + cfg["vocab_size"] * D)
    vectors = (2 * cfg["num_hidden_layers"] + 1) * D
    return matrices, vectors
