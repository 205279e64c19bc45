"""Seeded synthetic token streams, one per node and data set.

Zipf-distributed tokens of which half follow a node-specific bigram rule
(token t tends to be followed by (t + shift) mod V), so the nodes'
distributions differ.  The same seed gives the same tokens; every seed
gives the same shapes.
"""

from __future__ import annotations

import numpy as np


def node_batch(vocab: int, seq: int, batch: int, seed: int, data_set: int,
               node: int, zipf_a: float) -> dict:
    rng = np.random.default_rng([seed, data_set, node])
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    probs = 1.0 / ranks**zipf_a
    probs /= probs.sum()
    shift = 1 + (node * 17) % max(1, vocab // 4)
    base = rng.choice(vocab, size=(batch, seq), p=probs)
    follow = rng.random((batch, seq)) < 0.5
    shifted = np.roll(base, 1, axis=1)
    tokens = np.where(follow, (shifted + shift) % vocab, base)
    tokens[:, 0] = base[:, 0]
    labels = np.roll(tokens, -1, axis=1)
    labels[:, -1] = 0
    return {"tokens": tokens.astype(np.int32), "labels": labels.astype(np.int32)}


def node_data(vocab: int, traffic: dict, seed: int, data_set: int) -> dict:
    """Node-stacked {"tokens", "labels"} of shape (m, batch, seq) as numpy.
    ``data_set`` 0 is the lower level's training set D_g, 1 the upper
    level's validation set D_f."""
    m, seq, batch = traffic["nodes"], traffic["seq"], traffic["batch"]
    rows = [
        node_batch(vocab, seq, batch, seed, data_set, i, traffic["zipf_a"])
        for i in range(m)
    ]
    return {k: np.stack([r[k] for r in rows]) for k in ("tokens", "labels")}
