"""Gossip mixing engines.

Two interchangeable implementations of the consensus operator
``mix_delta(X)[i] = sum_j w_ij (X_j - X_i)``:

* ``dense``  — node-stacked matmul against (W - I).  Works for any graph;
  this is the simulator / reference form (the paper's own experiments run
  10 processes, so dense W is exact and cheap).
* ``ppermute`` — TPU-native: for static shift-structured topologies (ring,
  2-hop, torus) the neighbor exchange is a handful of
  ``jax.lax.ppermute`` calls inside ``shard_map`` — the native ICI pattern.
  Equivalence with dense is tested in tests/test_gossip.py.

The mixing *step* used by the algorithms is
``x <- x + gamma * mix_delta(x)``  i.e.  x <- (I + gamma (W - I)) x,
whose spectral gap is >= gamma * rho (paper Proposition 5).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.topology import Topology
from repro.core.types import Pytree
from repro.obs.scopes import scope


def mix_delta_dense(W: jax.Array, x: Pytree) -> Pytree:
    """sum_j w_ij (x_j - x_i) for node-stacked pytrees (leading axis m)."""

    def leaf(v):
        flat = v.reshape(v.shape[0], -1).astype(jnp.float32)
        out = (W - jnp.eye(W.shape[0], dtype=W.dtype)) @ flat
        # mixing arithmetic in f32, emitted at the parameter dtype (bf16 LMs)
        return out.reshape(v.shape).astype(v.dtype)

    with scope("exchange"):
        return jax.tree.map(leaf, x)


def mix_step_dense(W: jax.Array, gamma, x: Pytree) -> Pytree:
    """x + gamma * sum_j w_ij (x_j - x_i)."""
    with scope("exchange"):
        delta = mix_delta_dense(W, x)
        return jax.tree.map(lambda v, d: v + gamma * d, x, delta)


# ---------------------------------------------------------------------------
# shard_map / ppermute engine
# ---------------------------------------------------------------------------


def mix_delta_ppermute(topo: Topology, axis_name: str, x_local: Pytree) -> Pytree:
    """Per-rank neighbor-difference for shift-structured topologies.

    Must be called inside shard_map over ``axis_name`` whose size is topo.m.
    x_local leaves have NO node axis (they are this rank's copy).
    """
    if topo.ppermute_schedule is None:
        raise ValueError(f"topology {topo.name} has no static ppermute schedule")
    m = topo.m

    def leaf(v):
        acc = jnp.zeros_like(v)
        for shift, w in topo.ppermute_schedule:
            perm = [((r - shift) % m, r) for r in range(m)]  # receive from r-shift
            neighbor = jax.lax.ppermute(v, axis_name, perm)
            acc = acc + w * (neighbor - v)
        return acc

    with scope("exchange"):
        return jax.tree.map(leaf, x_local)


def mix_delta_allgather(topo: Topology, axis_name: str, x_local: Pytree) -> Pytree:
    """General-graph fallback inside shard_map: all_gather + weighted reduce."""
    with scope("exchange"):
        W = jnp.asarray(topo.W, dtype=jnp.float32)
        idx = jax.lax.axis_index(axis_name)
        row = W[idx] - jax.nn.one_hot(idx, topo.m)

        def leaf(v):
            stacked = jax.lax.all_gather(v, axis_name)  # (m, ...)
            return jnp.tensordot(
                row, stacked.astype(jnp.float32), axes=1
            ).astype(v.dtype)

        return jax.tree.map(leaf, x_local)


def mix_step_shard(topo: Topology, axis_name: str, gamma, x_local: Pytree) -> Pytree:
    fn = (
        mix_delta_ppermute
        if topo.ppermute_schedule is not None
        else mix_delta_allgather
    )
    with scope("exchange"):
        delta = fn(topo, axis_name, x_local)
        return jax.tree.map(lambda v, d: v + gamma * d, x_local, delta)
