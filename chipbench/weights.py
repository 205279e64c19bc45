"""Seeded weights in the program's parameter layout, made on the device.

The layout is the one `repro.models.transformer.init_lm_params` builds:
``{"embed", "blocks": [one dict per period position, stacked over the
repeats], "final_norm", "lm_head"}``; the bilevel split puts
``final_norm`` and ``lm_head`` in the lower variable y and the rest in
the upper variable x.  Each leaf is drawn from ``fold_in(key, leaf
index)``, so one leaf can be made again alone.  Only the layer kinds
listed here are covered; a configuration of another kind brings its own
entry.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

HEAD_KEYS = ("final_norm", "lm_head")
D_CONV = 4


class Leaf(NamedTuple):
    shape: tuple
    dtype: str
    init: str      # normal | ones | zeros | log_uniform_1_16
    scale: float = 1.0


def _dense(i, o, dt, R):
    return Leaf((R, i, o), dt, "normal", 1.0 / math.sqrt(i))


def _block(mc: dict, kind: str, R: int) -> dict:
    d, dt = mc["d_model"], mc["dtype"]
    blk = {"norm1": Leaf((R, d), dt, "ones")}
    if kind == "full":
        H, KV, hd = mc["num_heads"], mc["num_kv_heads"], mc["head_dim"]
        blk["attn"] = {
            "wq": _dense(d, H * hd, dt, R), "wk": _dense(d, KV * hd, dt, R),
            "wv": _dense(d, KV * hd, dt, R), "wo": _dense(H * hd, d, dt, R),
        }
    elif kind == "mamba":
        H, P, N, G = (mc["ssm_heads"], mc["ssm_head_dim"], mc["ssm_state"],
                      mc["ssm_groups"])
        d_inner = H * P
        conv_dim = d_inner + 2 * G * N
        blk["mamba"] = {
            "w_in": _dense(d, 2 * d_inner + 2 * G * N + H, dt, R),
            "w_out": _dense(d_inner, d, dt, R),
            "conv_w": Leaf((R, D_CONV, conv_dim), dt, "normal",
                           1.0 / math.sqrt(D_CONV)),
            "a_log": Leaf((R, H), "float32", "log_uniform_1_16"),
            "d_skip": Leaf((R, H), "float32", "ones"),
            "dt_bias": Leaf((R, H), "float32", "zeros"),
            "norm": Leaf((R, d_inner), dt, "ones"),
        }
    else:
        raise ValueError(f"no weight layout for layer kind {kind!r}")
    if mc.get("d_ff", 0) > 0:
        f = mc["d_ff"]
        blk["norm2"] = Leaf((R, d), dt, "ones")
        blk["mlp"] = {"wi": _dense(d, f, dt, R), "wg": _dense(d, f, dt, R),
                      "wo": _dense(f, d, dt, R)}
    return blk


def leaf_specs(mc: dict) -> dict:
    d, V, dt = mc["d_model"], mc["vocab_size"], mc["dtype"]
    P = len(mc["pattern"])
    R = mc["num_layers"] // P
    return {
        "embed": Leaf((V, d), dt, "normal", 0.02),
        "blocks": [_block(mc, kind, R) for kind in mc["pattern"]],
        "final_norm": Leaf((d,), dt, "ones"),
        "lm_head": Leaf((d, V), dt, "normal", 1.0 / math.sqrt(d)),
    }


def is_leaf(v):
    return isinstance(v, Leaf)


def make_leaf(spec: Leaf, key):
    dt = jnp.dtype(spec.dtype)
    if spec.init == "normal":
        v = jax.random.normal(key, spec.shape, jnp.float32) * spec.scale
    elif spec.init == "ones":
        v = jnp.ones(spec.shape, jnp.float32)
    elif spec.init == "zeros":
        v = jnp.zeros(spec.shape, jnp.float32)
    elif spec.init == "log_uniform_1_16":
        v = jnp.log(jax.random.uniform(key, spec.shape, jnp.float32, 1.0, 16.0))
    else:
        raise ValueError(spec.init)
    return v.astype(dt)


def make_params(mc: dict, key):
    """One node's parameters (traceable: call under jit)."""
    specs = leaf_specs(mc)
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_leaf)
    out = [make_leaf(s, jax.random.fold_in(key, i)) for i, s in enumerate(leaves)]
    return jax.tree.unflatten(treedef, out)


def split(params):
    x = {k: v for k, v in params.items() if k not in HEAD_KEYS}
    y = {k: v for k, v in params.items() if k in HEAD_KEYS}
    return x, y


def node_stacked(tree, m: int):
    return jax.tree.map(lambda v: jnp.broadcast_to(v[None], (m,) + v.shape), tree)


def make_xy(mc: dict, key, m: int):
    """Node-stacked (x0, y0): every node starts from the same point."""
    x, y = split(make_params(mc, key))
    return node_stacked(x, m), node_stacked(y, m)


def seed_key(seed: int):
    """A PRNG key from a seed of any size (two 32-bit folds)."""
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)
