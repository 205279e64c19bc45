"""Attention layer ("full"), as `repro.models.attention` with kind "full":
RMSNorm, causal grouped-query attention (query head h reads key/value head
h // (H / KV), as the program's `_gqa_scores` groups them) with rotary
positions on queries and keys (halves convention) unless the configuration
sets ``use_rope`` false, pre-norm residual; then the SwiGLU MLP sub-block
where ``d_ff > 0`` (`chipbench.kinds._swiglu`).

FLOPs: 2 x the block's matrix parameters x tokens, plus attention scores
and their product with V by the PaLM convention (arXiv:2204.02311, App.
B): 4 x heads x head_dim x seq per token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.kinds import _swiglu
from chipbench.reference.norm import rms_norm
from chipbench.weights import Leaf, dense_leaf

#: settings of the program's attention that this module does not cover
UNCOVERED = ("qkv_bias", "attn_softcap")


def _heads(mc: dict):
    bad = [k for k in UNCOVERED if mc.get(k)]
    if bad:
        raise ValueError(f"the 'full' kind module does not cover {bad}")
    return mc["num_heads"], mc["num_kv_heads"], mc["head_dim"]


def leaves(mc: dict, R: int, p: int) -> dict:
    d, dt = mc["d_model"], mc["dtype"]
    H, KV, hd = _heads(mc)
    return {
        "norm1": Leaf((R, d), dt, "ones"),
        "attn": {"wq": dense_leaf(d, H * hd, dt, R), "wk": dense_leaf(d, KV * hd, dt, R),
                 "wv": dense_leaf(d, KV * hd, dt, R), "wo": dense_leaf(H * hd, d, dt, R)},
        **_swiglu.leaves(mc, R, p),
    }


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
    H, KV, hd = _heads(mc)
    q = mm("bsd,de->bse", h, p["wq"]).reshape(B, S, H, hd)
    k = mm("bsd,de->bse", h, p["wk"]).reshape(B, S, KV, hd)
    v = mm("bsd,de->bse", h, p["wv"]).reshape(B, S, KV, hd)
    if mc.get("use_rope", True):
        q, k = rope(q, mc["rope_theta"]), rope(k, mc["rope_theta"])
    k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    s = mm("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = mm("bhqk,bkhd->bqhd", pr, v).reshape(B, S, H * hd)
    return mm("bse,ed->bsd", o, p["wo"])


def layer(blk, mc, x, mm):
    x = x + attention(blk["attn"], mc, rms_norm(x, blk["norm1"], mc["norm_eps"]), mm)
    return _swiglu.apply(blk, mc, x, mm)


def forward_flops(mc: dict, seq: int, tokens: int, p: int) -> float:
    d = mc["d_model"]
    H, KV, hd = _heads(mc)
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    return 2.0 * (attn + _swiglu.params(mc, p)) * tokens + 4.0 * H * hd * seq * tokens
