"""TPU-native C2DFB engine: nodes = mesh ranks under `shard_map`.

The node-stacked simulator in inner_loop.py/c2dfb.py is the reference; this
module runs the SAME update rules with each node's state living on its own
mesh rank, gossip realized as `lax.ppermute` (ring/2-hop/torus) or an
all_gather fallback, and compression applied rank-locally.  Equivalence
with the simulator is asserted in tests/test_distributed.py on forced host
devices.

This is the deployment path on a real pod: the "nodes" axis is the
(pod, data) product, the model inside each node is further sharded over
"model" (the inner pjit), and only compressed residuals cross node
boundaries — the paper's protocol, ICI/DCI-native.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core.compression import Compressor
from repro.core.gossip import mix_delta_allgather, mix_delta_ppermute
from repro.core.inner_loop import InnerState
from repro.core.topology import Topology
from repro.core.types import Pytree


def _mix(topo, axis, local):
    if topo.ppermute_schedule is not None:
        return mix_delta_ppermute(topo, axis, local)
    return mix_delta_allgather(topo, axis, local)


def _compress_local(compressor: Compressor, key: jax.Array, tree: Pytree, axis: str):
    """Per-rank compression with a rank-decorrelated key."""
    idx = jax.lax.axis_index(axis)
    key = jax.random.fold_in(key, idx)
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef, [compressor(k, leaf) for k, leaf in zip(keys, leaves)]
    )


def inner_step_shard(
    state: InnerState,
    key: jax.Array,
    grad_fn: Callable[[Pytree], Pytree],
    topo: Topology,
    axis: str,
    compressor: Compressor,
    gamma: float,
    eta: float,
) -> InnerState:
    """One Algorithm-2 step on a single rank (call inside shard_map).

    state leaves carry NO node axis; grad_fn computes THIS rank's gradient
    (its closure holds the rank-local data shard).
    """
    kd, ks = jax.random.split(key)

    mix_d = _mix(topo, axis, state.d_hat)
    d_new = jax.tree.map(
        lambda d, md, s: d + gamma * md - eta * s, state.d, mix_d, state.s
    )
    resid_d = jax.tree.map(jnp.subtract, d_new, state.d_hat)
    q_d = _compress_local(compressor, kd, resid_d, axis)
    d_hat_new = jax.tree.map(jnp.add, state.d_hat, q_d)

    g_new = grad_fn(d_new)
    mix_s = _mix(topo, axis, state.s_hat)
    s_new = jax.tree.map(
        lambda s, ms, gn, gp: s + gamma * ms + gn - gp,
        state.s, mix_s, g_new, state.g_prev,
    )
    resid_s = jax.tree.map(jnp.subtract, s_new, state.s_hat)
    q_s = _compress_local(compressor, ks, resid_s, axis)
    s_hat_new = jax.tree.map(jnp.add, state.s_hat, q_s)

    return InnerState(d=d_new, d_hat=d_hat_new, s=s_new, s_hat=s_hat_new, g_prev=g_new)


def make_sharded_inner_loop(
    mesh: Mesh,
    topo: Topology,
    axis: str,
    grad_fn_local: Callable,
    compressor: Compressor,
    gamma: float,
    eta: float,
    K: int,
):
    """Returns a jitted fn(state_stacked, key, data_stacked) -> state_stacked.

    state/data are node-stacked on the host (leading axis m); shard_map
    splits them so each rank holds its slice, runs K compressed-GT steps
    with ppermute gossip, and returns the re-stacked state.
    """

    def per_rank(state, key, data):
        # state/data leaves keep a leading axis of size 1 per rank; drop it
        state = jax.tree.map(lambda v: v[0], state)
        data = jax.tree.map(lambda v: v[0], data)
        gfn = lambda d: grad_fn_local(d, data)

        def body(st, k):
            return inner_step_shard(
                st, k, gfn, topo, axis, compressor, gamma, eta
            ), None

        keys = jax.random.split(key, K)
        state, _ = jax.lax.scan(body, state, keys)
        return jax.tree.map(lambda v: v[None], state)

    spec = P(axis)
    fn = jax.shard_map(
        per_rank,
        mesh=mesh,
        in_specs=(spec, P(), spec),
        out_specs=spec,
        check_vma=False,
    )
    return jax.jit(fn)
