"""Staleness-aware gossip mixing — the jit/scan half of the async engine.

Under asynchronous execution a node mixes whatever neighbor reference
points have actually ARRIVED, not the current ones.  Because reference
points evolve by cumulative residual updates, "the copy of j that i holds"
is simply j's reference at an earlier version; the engine therefore carries
a rolling HISTORY of the node-stacked reference pytree (leading axis =
version age) and gates the mixing matrix with a per-edge integer age.

The delayed operator implemented here is the *pairwise-version* form

    mix_i = sum_j w_ij ( h[a_ij, j] - h[a_ij, i] )

where ``a_ij`` is the age of edge (i, j)'s newest COMMONLY-held version
(symmetric: a_ij == a_ji, realized in a deployment by sequence-numbered
acks).  Node i subtracts its OWN value at that same version — it keeps its
full local history, so this costs no communication.  The symmetry is what
preserves the paper's mean-dynamics invariant (Eq. 7) exactly: for every
unordered pair the two terms cancel in the node average, so

    d_bar^{k+1} = d_bar^k - eta * s_bar^k

holds under ANY symmetric delay pattern, exactly as in the synchronous
protocol (property-tested in tests/test_async_invariants.py).  With all
ages zero the operator reduces to ``mix_delta_dense`` on the current
references.

STALENESS-ADAPTIVE DAMPING.  Delayed gossip is only contractive while
``gamma * staleness`` stays small (test_delayed_consensus_stability): an
age-a edge applies an old disagreement direction, and a large mixing step
along it overshoots.  ``damp_weights`` therefore scales each edge's weight
by a decreasing function of its CURRENT age —

    none         w_ij                      (the undamped PR-2 operator)
    inverse-age  w_ij / (1 + a_ij)
    exp-decay    w_ij * decay ** a_ij      (decay in (0, 1], default 0.5)

— and renormalizes by absorbing the removed mass into the diagonal
(W'_ii = 1 - sum_{j != i} W'_ij), so every per-step realized matrix stays
symmetric, row-stochastic and non-negative: each step remains a valid
Assumption-1 gossip operator.  Because the ages are symmetric, the damping
factor is symmetric too, so the pairwise cancellation above — and with it
the Eq. 7 mean-dynamics invariant — is preserved by construction.  Zero
ages give a damping factor of exactly 1.0, so the damped operator is
BIT-exact with the undamped one (property-tested in
tests/test_adaptive_mixing_property.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import Pytree
from repro.obs.scopes import scope

#: Staleness-adaptive damping policies for the delayed mixing operator.
DAMPING_POLICIES = ("none", "inverse-age", "exp-decay")


def validate_damping(policy: str) -> str:
    """Reject unknown damping policies up front (before a run starts),
    with the one canonical error message; returns the policy."""
    if policy not in DAMPING_POLICIES:
        raise ValueError(
            f"unknown mixing_damping {policy!r}; have {DAMPING_POLICIES}"
        )
    return policy


def damping_factor(
    ages: jax.Array, policy: str, decay: float = 0.5
) -> jax.Array:
    """Per-edge weight multiplier phi(a) in (0, 1], with phi(0) == 1.0
    exactly (IEEE: x * 1.0 == x, so zero-age edges are undamped bit-for-
    bit).  ``ages`` is any integer array; the factor has its shape."""
    validate_damping(policy)
    a = jnp.asarray(ages, jnp.float32)
    if policy == "none":
        return jnp.ones_like(a)
    if policy == "inverse-age":
        return 1.0 / (1.0 + a)
    if not 0.0 < decay <= 1.0:
        raise ValueError(f"exp-decay needs decay in (0, 1], got {decay}")
    return jnp.asarray(decay, jnp.float32) ** a


def damp_weights(
    W: jax.Array, ages: jax.Array, policy: str, decay: float = 0.5
) -> jax.Array:
    """The realized age-damped mixing matrix: off-diagonal
    ``W'_ij = W_ij * phi(a_ij)``, diagonal renormalized to
    ``1 - sum_{j != i} W'_ij``.  Symmetric (ages and W are), row- and
    column-stochastic, non-negative — phi <= 1 only moves mass onto the
    diagonal.  With ``policy="none"`` returns ``W`` unchanged (bit-exact
    fast path)."""
    if policy == "none":
        return W
    m = W.shape[0]
    eye = jnp.eye(m, dtype=W.dtype)
    off = W * (1.0 - eye) * damping_factor(ages, policy, decay).astype(W.dtype)
    return off + jnp.diag(1.0 - off.sum(axis=1))


def required_depth(policy: str, bound: int, K: int, max_lag: int = 0) -> int:
    """STATIC history depth a K-step delayed loop must carry under a
    gating policy — the one sizing rule every consumer (the scheduler's
    ``depth_for``, the eager engine, the compiled `lax.scan` runtime)
    shares, so the jit-side history shapes are fixed before any round
    runs.

    With ``max_lag`` > 0 (edges re-entering from a topology schedule, or
    lag carried in by an injected scheduler) every realizable age is
    bounded by (K - 1) + max_lag for the never-waiting full policy, and by
    the bound for bounded (whose gate also admits lag-old versions while
    lag <= bound - k); the +1 everywhere covers age 0 (the current
    version).  Sync ages are provably zero, so one slot always suffices.
    """
    if policy == "sync" or max_lag <= 0:
        if policy == "full":
            return max(1, K)
        if policy == "bounded":
            return min(bound + 1, max(1, K))
        return 1
    max_possible_age = K - 1 + max_lag
    if policy == "full":
        return max_possible_age + 1
    return min(bound, max_possible_age) + 1


def deterministic_ages(
    K: int, S: int, lag: np.ndarray, neighbors,
) -> np.ndarray:
    """Closed-form (K, m, m) age tensor for the scheduler's
    ``version_rule="deterministic"``: at step k every active edge mixes
    exactly version ``k - S`` (S = the staleness bound, 0 for sync),
    clipped under churn to the catch-up version 0 while ``k - S`` is not
    yet a positive in-round version, and to the frozen pre-dropout version
    ``-lag`` while the bound still admits it (``k - S <= -lag`` — the same
    condition the bounded gate uses to skip the catch-up wait).

    The result is a pure function of (k, S, lag): both endpoints can
    compute it locally with no coordination, it is symmetric by
    construction (lag is), and every age is <= max(S, 0) — so the realized
    damped operator stays a valid Assumption-1 gossip matrix and fits the
    `required_depth` history sizing unchanged.  ``neighbors`` is the
    loop's ACTIVE per-node neighbor lists; non-edges stay age 0 (ignored
    by the weighting, same convention as the scheduler's common rule).
    """
    m = len(neighbors)
    lag = np.asarray(lag, dtype=np.int64)
    ages = np.zeros((K, m, m), dtype=np.int32)
    for k in range(K):
        for i in range(m):
            for j in neighbors[i]:
                if j < i:
                    continue  # fill symmetric pairs once
                v = k - S
                if v < 1:
                    v = 0 if v > -int(lag[i, j]) else -int(lag[i, j])
                ages[k, i, j] = ages[k, j, i] = k - v
    return ages


def init_history(tree: Pytree, depth: int) -> Pytree:
    """(depth, m, ...) history with every slot holding the current version.

    Slot 0 is the newest version; at local step k slot ``a`` holds version
    ``k - a`` (clamped at the round's initial version, which is what every
    slot starts as — correct because age <= step by construction).
    """
    return jax.tree.map(
        lambda v: jnp.broadcast_to(v[None], (depth,) + v.shape).copy(), tree
    )


def push_history(hist: Pytree, new: Pytree) -> Pytree:
    """Shift the history one version: slot 0 becomes ``new``."""
    return jax.tree.map(
        lambda h, n: jnp.concatenate([n[None], h[:-1]], axis=0), hist, new
    )


def mix_delta_delayed(
    W: jax.Array,
    hist: Pytree,
    ages: jax.Array,
    damping: str = "none",
    decay: float = 0.5,
) -> Pytree:
    """sum_j w'_ij (h[a_ij, j] - h[a_ij, i]) for a history pytree.

    ``ages`` is an (m, m) int array of per-edge version ages, symmetric and
    < history depth; entries on non-edges (w_ij = 0) and the diagonal are
    ignored by the weighting.  ``damping`` selects the staleness-adaptive
    weight policy (``DAMPING_POLICIES``); the diagonal renormalization of
    `damp_weights` never enters the delta form (the i == i term is zero),
    so the realized operator is exactly ``I + (W' - I)`` applied to the
    age-gated views.  Arithmetic in f32, emitted at the leaf dtype (same
    contract as ``mix_delta_dense``).
    """
    with scope("exchange"):
        m = ages.shape[0]
        rows = jnp.arange(m)[:, None]
        cols = jnp.arange(m)[None, :]
        Wf = W.astype(jnp.float32)
        if damping != "none":
            Wf = Wf * damping_factor(ages, damping, decay)

        def leaf(h):
            flat = h.reshape(h.shape[0], m, -1).astype(jnp.float32)
            theirs = flat[ages, cols]  # (m, m, d): h[a_ij, j]
            mine = flat[ages, rows]    # (m, m, d): h[a_ij, i]
            out = jnp.einsum("ij,ijd->id", Wf, theirs - mine)
            return out.reshape(h.shape[1:]).astype(h.dtype)

        return jax.tree.map(leaf, hist)
