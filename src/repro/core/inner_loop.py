"""Algorithm 2 — the compressed gradient-tracking inner loop ``IN``.

State per node (stacked over the leading node axis):
    d      current model (y or z)
    d_hat  reference point of the model (what neighbors believe we hold)
    s      gradient tracker
    s_hat  reference point of the tracker
    g_prev gradient at the previous iterate (tracking delta)

One step (paper Algorithm 2):
    d^{k+1}    = d^k + gamma * sum_j w_ij (dhat_j - dhat_i) - eta * s^k
    transmit   Q(d^{k+1} - dhat^k);   dhat^{k+1} = dhat^k + Q(.)
    s^{k+1}    = s^k + gamma * sum_j w_ij (shat_j - shat_i) + grad^{k+1} - grad^k
    transmit   Q(s^{k+1} - shat^k);   shat^{k+1} = shat^k + Q(.)

Key invariants (tested):
* mean dynamics are compression-free:  d_bar^{k+1} = d_bar^k - eta * s_bar^k  (Eq. 7)
* tracking:                            s_bar^k = (1/m) sum_i grad_i(d_i^k)   (Prop. 4)

Reference points and trackers PERSIST across outer rounds (Algorithm 1 passes
(dhat^K)^t back in).  Because the objective changes between rounds (x moved),
``refresh_tracker`` re-bases the tracker with grad_new - grad_prev, which
preserves the tracking invariant exactly.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.compression import Compressor
from repro.core.gossip import mix_delta_dense
from repro.core.types import Pytree, consensus_error, tree_sq_norm
from repro.obs.scopes import scope


class InnerState(NamedTuple):
    d: Pytree
    d_hat: Pytree
    s: Pytree
    s_hat: Pytree
    g_prev: Pytree


def compress_stacked(compressor: Compressor, key: jax.Array, tree: Pytree) -> Pytree:
    """Apply Q per node (vmap over the leading node axis, per-node keys)."""
    leaves, treedef = jax.tree.flatten(tree)
    m = leaves[0].shape[0]
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, leaf in zip(keys, leaves):
        node_keys = jax.random.split(k, m)
        out.append(jax.vmap(compressor)(node_keys, leaf))
    return jax.tree.unflatten(treedef, out)


def inner_init(d0: Pytree, grad_fn: Callable[[Pytree], Pytree]) -> InnerState:
    """Fresh state: references start at the true values (zero residual),
    tracker starts at the local gradient (standard GT init)."""
    g0 = grad_fn(d0)
    return InnerState(d=d0, d_hat=d0, s=g0, s_hat=g0, g_prev=g0)


def refresh_tracker(state: InnerState, grad_fn) -> InnerState:
    """Re-base the tracker after the objective changed (new outer x).

    s += grad_new(d) - grad_prev keeps  s_bar == mean grad  under the NEW
    objective, while reference points persist (their residuals stay small —
    that is the whole point of the reference-point protocol)."""
    g_new = grad_fn(state.d)
    with scope("update"):
        s = jax.tree.map(
            lambda s_, gn, gp: s_ + gn - gp, state.s, g_new, state.g_prev
        )
    return state._replace(s=s, g_prev=g_new)


def inner_transmit(
    compressor: Compressor, key: jax.Array, value: Pytree, ref: Pytree
) -> Pytree:
    """The transmit half of a step: the compressed residual ``Q(value - ref)``.

    This IS the per-edge message payload — every neighbor receives the same
    residual and applies it to its copy of the sender's reference point.
    """
    with scope("compress"):
        resid = jax.tree.map(jnp.subtract, value, ref)
        return compress_stacked(compressor, key, resid)


def inner_apply(
    state: InnerState,
    key: jax.Array,
    grad_fn: Callable[[Pytree], Pytree],
    compressor: Compressor,
    gamma: float,
    eta: float,
    mix_d: Pytree,
    mix_s: Pytree,
) -> tuple[InnerState, tuple[Pytree, Pytree]]:
    """One inner step with the MIXING DELTAS supplied by the caller.

    This is the mix/transmit split: the synchronous path feeds
    ``mix_delta_dense`` of the current references, the async engine
    (`repro.async_gossip`) feeds staleness-gated deltas built from reference
    histories and per-edge arrival times (optionally with age-damped
    weights — `mixing.DAMPING_POLICIES`).  Also returns the two transmitted
    messages ``(q_d, q_s)`` so callers can meter exact per-message bytes
    inside the scan (`repro.net.wire.scan_tree_bytes`).
    """
    kd, ks = jax.random.split(key)

    # (1) model update: mix on REFERENCES, descend along tracker
    with scope("update"):
        d_new = jax.tree.map(
            lambda d, md, s: d + gamma * md - eta * s, state.d, mix_d, state.s
        )

    # (2) reference update via compressed residual (this is the transmission)
    q_d = inner_transmit(compressor, kd, d_new, state.d_hat)
    with scope("compress"):
        d_hat_new = jax.tree.map(jnp.add, state.d_hat, q_d)

    # (3) tracker update: mix on tracker references + gradient delta
    g_new = grad_fn(d_new)
    with scope("update"):
        s_new = jax.tree.map(
            lambda s, ms, gn, gp: s + gamma * ms + gn - gp,
            state.s,
            mix_s,
            g_new,
            state.g_prev,
        )

    # (4) tracker reference update via compressed residual
    q_s = inner_transmit(compressor, ks, s_new, state.s_hat)
    with scope("compress"):
        s_hat_new = jax.tree.map(jnp.add, state.s_hat, q_s)

    new_state = InnerState(
        d=d_new, d_hat=d_hat_new, s=s_new, s_hat=s_hat_new, g_prev=g_new
    )
    return new_state, (q_d, q_s)


def inner_step(
    state: InnerState,
    key: jax.Array,
    grad_fn: Callable[[Pytree], Pytree],
    W: jax.Array,
    compressor: Compressor,
    gamma: float,
    eta: float,
) -> InnerState:
    """Synchronous step: mix on the current references, then apply."""
    mix_d = mix_delta_dense(W, state.d_hat)
    mix_s = mix_delta_dense(W, state.s_hat)
    new_state, _ = inner_apply(
        state, key, grad_fn, compressor, gamma, eta, mix_d, mix_s
    )
    return new_state


def inner_loop(
    state: InnerState,
    key: jax.Array,
    grad_fn: Callable[[Pytree], Pytree],
    W: jax.Array,
    compressor: Compressor,
    gamma: float,
    eta: float,
    K: int,
    fabric=None,
    round_idx: int = 0,
    transport=None,
) -> tuple[InnerState, dict]:
    """Run K compressed-GT steps via lax.scan; returns final state + metrics.

    Metrics always include ``msg_bytes`` — the exact wire bytes this loop's
    K x 2 messages put on the network (per-node broadcast accounting),
    counted INSIDE the scan by `repro.net.wire.scan_tree_bytes` (a jit
    nnz/byte counter), not a host-side steady-state estimate.

    `repro.async_gossip.engine.async_inner_loop` mirrors this scan body
    with a staleness-gated (and optionally age-damped) mix plus a history
    carry that can persist ACROSS rounds under topology schedules — keep
    the two bodies and their metrics keys in lockstep.

    With a ``repro.net.fabric.NetworkFabric`` (eager mode only — the fabric
    is host-side numpy), metrics additionally carry ``wire_bytes`` (exact
    integer, codec-measured on this loop's residuals) and ``sim_seconds``
    (the simulated wall clock of the K barrier phases x 2 messages).
    ``transport`` (a `repro.transport.Transport`) prices the loop through
    the transport's fabric-mirroring face instead — same metrics, backend-
    agnostic; for a device-EXECUTED loop see
    `repro.transport.device.make_device_round` (its `_device_inner_loop`
    mirrors this scan body)."""
    from repro.net.wire import scan_tree_bytes

    if transport is not None:
        if fabric is not None:
            raise ValueError("pass fabric OR transport, not both")
        fabric = transport  # Transport mirrors the fabric pricing API

    def body(st, k):
        mix_d = mix_delta_dense(W, st.d_hat)
        mix_s = mix_delta_dense(W, st.s_hat)
        st, (q_d, q_s) = inner_apply(
            st, k, grad_fn, compressor, gamma, eta, mix_d, mix_s
        )
        nbytes = scan_tree_bytes(compressor, q_d) + scan_tree_bytes(
            compressor, q_s
        )
        return st, nbytes

    keys = jax.random.split(key, K)
    state, step_bytes = jax.lax.scan(body, state, keys)
    with scope("meter"):
        metrics = {
            "consensus_err": consensus_error(state.d),
            "compress_err": tree_sq_norm(
                jax.tree.map(jnp.subtract, state.d, state.d_hat)
            ),
            "msg_bytes": jnp.sum(step_bytes),
        }
    if fabric is not None:
        phases, labels = inner_round_phases(state, compressor, fabric.topo, key, K)
        rep = fabric.simulate_round(phases, round_idx, labels=labels)
        metrics["wire_bytes"] = rep["wire_bytes"]
        metrics["sim_seconds"] = rep["sim_seconds"]
    return state, metrics


def inner_message_bytes(
    state: InnerState, compressor: Compressor, key: jax.Array
) -> tuple[list[int], list[int]]:
    """Exact per-node wire bytes of one inner step's two transmissions,
    measured by serializing Q(d - d_hat) and Q(s - s_hat) with the codec
    (current residuals; sizes are steady once residuals are nonzero)."""
    from repro.net.wire import codec_for

    codec = codec_for(compressor)
    kd, ks = jax.random.split(key)
    out = []
    for k_, a, b in ((kd, state.d, state.d_hat), (ks, state.s, state.s_hat)):
        resid = jax.tree.map(jnp.subtract, a, b)
        q = compress_stacked(compressor, k_, resid)
        m = jax.tree.leaves(q)[0].shape[0]
        out.append(
            [
                codec.tree_bytes(jax.tree.map(lambda v: v[i], q))
                for i in range(m)
            ]
        )
    return out[0], out[1]


def inner_round_phases(
    state: InnerState, compressor: Compressor, topo, key: jax.Array, K: int
) -> tuple[list, list]:
    """K steps x (d-residual, s-residual) barrier phases as per-edge byte
    dicts for ``NetworkFabric.simulate_round``."""
    from repro.net.fabric import edge_list

    bytes_d, bytes_s = inner_message_bytes(state, compressor, key)
    edges = edge_list(topo)
    phase_d = {(i, j): bytes_d[i] for (i, j) in edges}
    phase_s = {(i, j): bytes_s[i] for (i, j) in edges}
    phases, labels = [], []
    for k in range(K):
        phases += [phase_d, phase_s]
        labels += [f"in{k}/d", f"in{k}/s"]
    return phases, labels


def inner_wire_bytes_per_round(
    compressor: Compressor, single_node_tree: Pytree, K: int, m: int
) -> float:
    """Exact wire bytes one round of IN puts on the network (all m nodes):
    each node transmits Q(d-resid) and Q(s-resid) once per step."""
    per_msg = compressor.tree_wire_bytes(single_node_tree)
    return 2.0 * per_msg * K * m
