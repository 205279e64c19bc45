"""Gossip matrices of the reference, built from a traffic mix's
``topology`` and ``nodes``: Metropolis-Hastings weights on the graph,
w_ij = 1 / (1 + max(deg i, deg j)) on each edge and the rest of each row
on the diagonal (symmetric and doubly stochastic)."""

from __future__ import annotations

import numpy as np


def ring_edges(m: int) -> set:
    """Each node linked to its two immediate neighbours; on two nodes the
    two links are one edge."""
    return {tuple(sorted((i, (i + 1) % m))) for i in range(m) if m > 1}


EDGES = {"ring": ring_edges}


def metropolis(edges: set, m: int) -> np.ndarray:
    deg = np.zeros(m, int)
    for i, j in edges:
        deg[i] += 1
        deg[j] += 1
    W = np.zeros((m, m))
    for i, j in edges:
        W[i, j] = W[j, i] = 1.0 / (1 + max(deg[i], deg[j]))
    W[np.diag_indices(m)] = 1.0 - W.sum(axis=1)
    return W


def gossip_matrix(traffic: dict) -> np.ndarray:
    kind, m = traffic["topology"], traffic["nodes"]
    if kind not in EDGES:
        raise ValueError(f"the reference has no topology {kind!r}")
    return metropolis(EDGES[kind](m), m)
