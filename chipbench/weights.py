"""Seeded weights in the program's parameter layout, made on the device.

The layout is the one `repro.models.transformer.init_lm_params` builds:
``{"embed", "blocks": [one dict per period position, stacked over the
repeats], "final_norm", "lm_head"}``; the bilevel split puts
``final_norm`` and ``lm_head`` in the lower variable y and the rest in
the upper variable x.  Each leaf is drawn from ``fold_in(key, leaf
index)``, so one leaf can be made again alone.  A block's leaves come
from the module of its kind (`chipbench.kinds`).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from chipbench import kinds

HEAD_KEYS = ("final_norm", "lm_head")


class Leaf(NamedTuple):
    shape: tuple
    dtype: str
    init: str      # normal | ones | zeros | log_uniform_1_16
    scale: float = 1.0


def dense_leaf(i: int, o: int, dt: str, R: int) -> Leaf:
    """An (i, o) matrix stacked over R repeats, drawn with scale 1/sqrt(i)."""
    return Leaf((R, i, o), dt, "normal", 1.0 / math.sqrt(i))


def leaf_specs(mc: dict) -> dict:
    d, V, dt = mc["d_model"], mc["vocab_size"], mc["dtype"]
    R = mc["num_layers"] // len(mc["pattern"])
    return {
        "embed": Leaf((V, d), dt, "normal", 0.02),
        "blocks": [mod.leaves(mc, R, p) for p, mod in enumerate(kinds.modules(mc))],
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
