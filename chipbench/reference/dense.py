"""Plain decoder of the dense kind ("full" layers): RMSNorm, causal
multi-head attention with rotary positions (halves convention), SwiGLU
MLP, pre-norm residuals.  float32 throughout."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (B, S, H, hd); rotates the two halves of each head."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs  # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, mc, h, mm):
    B, S, _ = h.shape
    H, KV, hd = mc["num_heads"], mc["num_kv_heads"], mc["head_dim"]
    if KV != H:
        raise ValueError("the plain reference covers multi-head attention only")
    q = mm("bsd,de->bse", h, p["wq"]).reshape(B, S, H, hd)
    k = mm("bsd,de->bse", h, p["wk"]).reshape(B, S, H, hd)
    v = mm("bsd,de->bse", h, p["wv"]).reshape(B, S, H, hd)
    q, k = rope(q, mc["rope_theta"]), rope(k, mc["rope_theta"])
    s = mm("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = mm("bhqk,bkhd->bqhd", pr, v).reshape(B, S, H * hd)
    return mm("bse,ed->bsd", o, p["wo"])


def mlp(p, h, mm):
    a = jax.nn.silu(mm("bsd,df->bsf", h, p["wg"])) * mm("bsd,df->bsf", h, p["wi"])
    return mm("bsf,fd->bsd", a, p["wo"])


def layer(blk, mc, x, mm):
    eps = mc["norm_eps"]
    x = x + attention(blk["attn"], mc, rms_norm(x, blk["norm1"], eps), mm)
    return x + mlp(blk["mlp"], rms_norm(x, blk["norm2"], eps), mm)
