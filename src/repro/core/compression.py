"""Contractive compressors (paper Definition 2) and wire-byte metering.

A compressor ``Q`` must satisfy  E||Q(A) - A||^2 <= (1 - delta) ||A||^2  for
some delta in (0, 1].  Biased compressors are made contractive-compatible via
the paper's Proposition 1 rescaling  Q' = Q / (2 - delta).

All compressors operate leaf-wise on pytrees and are deterministic given a
PRNG key, so they can live inside jit/scan.  ``wire_bytes(tree)`` gives the
exact number of bytes a real DFL deployment would put on the wire for one
transmission of the compressed residual (the SPMD simulator moves dense
tensors; metering is the accounting abstraction — see DESIGN.md §3).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import Pytree

VALUE_BYTES = 4  # float32 payload
INDEX_BYTES = 4  # int32 index payload


class Compressor:
    """Interface.  ``delta`` is the contraction factor delta_c."""

    delta: float

    def __call__(self, key: jax.Array, x: jax.Array) -> jax.Array:
        raise NotImplementedError

    def leaf_wire_bytes(self, size: int) -> float:
        raise NotImplementedError

    # -- pytree conveniences ------------------------------------------------
    def compress_tree(self, key: jax.Array, tree: Pytree) -> Pytree:
        leaves, treedef = jax.tree.flatten(tree)
        keys = jax.random.split(key, len(leaves))
        out = [self(k, leaf) for k, leaf in zip(keys, leaves)]
        return jax.tree.unflatten(treedef, out)

    def tree_wire_bytes(self, tree: Pytree) -> float:
        return float(
            sum(self.leaf_wire_bytes(int(x.size)) for x in jax.tree.leaves(tree))
        )


@dataclasses.dataclass(frozen=True)
class Identity(Compressor):
    """No compression (delta = 1)."""

    delta: float = 1.0

    def __call__(self, key, x):
        return x

    def leaf_wire_bytes(self, size):
        return size * VALUE_BYTES


@dataclasses.dataclass(frozen=True)
class TopK(Compressor):
    """Exact global top-k by magnitude (the paper's experimental choice).

    ratio = k/d.  Biased; contractive with delta = ratio.
    """

    ratio: float = 0.2

    @property
    def delta(self):  # type: ignore[override]
        return self.ratio

    def __call__(self, key, x):
        flat = x.reshape(-1)
        d = flat.shape[0]
        k = max(1, int(round(self.ratio * d)))
        _, idx = jax.lax.top_k(jnp.abs(flat), k)
        mask = jnp.zeros_like(flat).at[idx].set(1.0)
        return (flat * mask).reshape(x.shape)

    def leaf_wire_bytes(self, size):
        k = max(1, int(round(self.ratio * size)))
        return k * (VALUE_BYTES + INDEX_BYTES)


@dataclasses.dataclass(frozen=True)
class BlockTopK(Compressor):
    """Per-block top-k — the TPU-native variant backed by the Pallas kernel.

    Splits the flattened leaf into blocks of ``block`` and keeps the top
    round(ratio*block) entries of each block (``jax.lax.top_k``: ties go
    to the lower index).  Still contractive with delta = ratio
    (property-tested).  This class is the *semantic* (jnp) form;
    `KernelBlockTopK` runs the sort-free Pallas kernel, which selects the
    same entries.
    """

    ratio: float = 0.2
    block: int = 1024

    @property
    def delta(self):  # type: ignore[override]
        return self.ratio

    def __call__(self, key, x):
        flat = x.reshape(-1)
        d = flat.shape[0]
        nb = -(-d // self.block)
        pad = nb * self.block - d
        padded = jnp.pad(flat, (0, pad)).reshape(nb, self.block)
        k = max(1, int(round(self.ratio * self.block)))
        _, idx = jax.lax.top_k(jnp.abs(padded), k)
        mask = jnp.zeros_like(padded)
        mask = jax.vmap(lambda m, i: m.at[i].set(1.0))(mask, idx)
        out = (padded * mask).reshape(-1)[:d]
        return out.reshape(x.shape)

    def leaf_wire_bytes(self, size):
        nb = -(-size // self.block)
        k = max(1, int(round(self.ratio * self.block)))
        # per-block local indices need only ceil(log2(block))/8 bytes; keep 4
        # for comparability with TopK.
        return nb * k * (VALUE_BYTES + INDEX_BYTES)


@dataclasses.dataclass(frozen=True)
class RandK(Compressor):
    """Uniformly random k coordinates, unbiased when rescaled by d/k.

    We use the *biased* (unscaled) form here, contractive with delta = ratio.
    """

    ratio: float = 0.2

    @property
    def delta(self):  # type: ignore[override]
        return self.ratio

    def __call__(self, key, x):
        flat = x.reshape(-1)
        d = flat.shape[0]
        k = max(1, int(round(self.ratio * d)))
        idx = jax.random.choice(key, d, shape=(k,), replace=False)
        mask = jnp.zeros_like(flat).at[idx].set(1.0)
        return (flat * mask).reshape(x.shape)

    def leaf_wire_bytes(self, size):
        k = max(1, int(round(self.ratio * size)))
        return k * (VALUE_BYTES + INDEX_BYTES)


@dataclasses.dataclass(frozen=True)
class StochasticQuant(Compressor):
    """Per-leaf-scaled stochastic uniform quantizer to ``bits`` bits.

    Unbiased, contractive: E||Q(x)-x||^2 <= (L^2/4) * ||x||_inf-ish bound; for
    the standard scale = max|x| scheme the variance is bounded by
    (d / (4 L^2)) * scale^2 ... we use the conservative per-leaf delta below
    and verify contraction empirically in tests.  Backed by the Pallas
    quantizer kernel on TPU (`repro.kernels.ops.quantize`).
    """

    bits: int = 4

    @property
    def delta(self):  # type: ignore[override]
        # levels L = 2^bits - 1; worst-case relative error 1/(2L) per entry
        levels = (1 << self.bits) - 1
        return max(1e-3, 1.0 - 1.0 / (2 * levels))

    def __call__(self, key, x):
        flat = x.reshape(-1)
        levels = (1 << self.bits) - 1
        scale = jnp.maximum(jnp.max(jnp.abs(flat)), 1e-12)
        y = flat / scale  # in [-1, 1]
        steps = (y + 1.0) * 0.5 * levels
        lo = jnp.floor(steps)
        p = steps - lo
        u = jax.random.uniform(key, flat.shape)
        q = lo + (u < p).astype(flat.dtype)
        deq = (q / levels) * 2.0 - 1.0
        return (deq * scale).reshape(x.shape)

    def leaf_wire_bytes(self, size):
        return size * self.bits / 8.0 + VALUE_BYTES  # payload + scale


@dataclasses.dataclass(frozen=True)
class LowRank(Compressor):
    """PowerSGD-style rank-r residual sketch (beyond-paper compressor).

    Reshape the leaf to ~square (n, m), one power iteration with a fixed
    random test matrix:  P = M Q0 (orthonormalized),  Q = M^T P,  Q(M) = P Q^T.
    Biased; contraction is data-dependent (residuals concentrate energy in a
    few directions as training converges) — delta below is the conservative
    bound r/min(n,m) used for wire accounting, and tests verify empirical
    contraction on generic inputs.
    """

    rank: int = 4

    @property
    def delta(self):  # type: ignore[override]
        return 1e-3  # conservative; see class docstring

    def _dims(self, d):
        n = int(np.floor(np.sqrt(d)))
        while d % n:
            n -= 1
        return n, d // n

    def _worth_it(self, d):
        n, m = self._dims(d)
        r = min(self.rank, n, m)
        return r * (n + m) < d  # sketch must beat dense

    def __call__(self, key, x):
        flat = x.reshape(-1)
        d = flat.shape[0]
        if not self._worth_it(d):
            return x  # skinny/small leaf — send dense
        n, m = self._dims(d)
        M = flat.reshape(n, m).astype(jnp.float32)
        r = min(self.rank, n, m)
        q0 = jax.random.normal(jax.random.PRNGKey(0), (m, r), jnp.float32)
        p = M @ q0
        p, _ = jnp.linalg.qr(p)
        q = M.T @ p
        out = (p @ q.T).reshape(-1)
        return out.astype(x.dtype).reshape(x.shape)

    def leaf_wire_bytes(self, size):
        if not self._worth_it(size):
            return size * VALUE_BYTES
        n, m = self._dims(size)
        r = min(self.rank, n, m)
        return r * (n + m) * VALUE_BYTES


@dataclasses.dataclass(frozen=True)
class Rescaled(Compressor):
    """Proposition 1:  for an UNBIASED contractive Q,  Q' = Q / (2 - delta)
    is a (biased) contractive compressor with delta' = 1/(2 - delta)."""

    inner: Any = None

    @property
    def delta(self):  # type: ignore[override]
        return 1.0 / (2.0 - self.inner.delta)

    def __call__(self, key, x):
        return self.inner(key, x) / (2.0 - self.inner.delta)

    def leaf_wire_bytes(self, size):
        return self.inner.leaf_wire_bytes(size)


@dataclasses.dataclass(frozen=True)
class KernelBlockTopK(Compressor):
    """BlockTopK backed by the Pallas kernel (threshold-bisection selection).

    Semantics = repro.kernels.ref.block_topk_ref: exactly k per block, the
    entries `BlockTopK` keeps; contractive with delta = ratio (see
    tests/test_kernels.py).
    """

    ratio: float = 0.2
    block: int = 1024

    @property
    def delta(self):  # type: ignore[override]
        return self.ratio

    def __call__(self, key, x):
        from repro.kernels.ops import block_topk

        return block_topk(x, ratio=self.ratio, block=self.block)

    def leaf_wire_bytes(self, size):
        nb = -(-size // self.block)
        k = max(1, int(round(self.ratio * self.block)))
        return nb * k * (VALUE_BYTES + INDEX_BYTES)


@dataclasses.dataclass(frozen=True)
class KernelQuant(Compressor):
    """StochasticQuant backed by the Pallas kernel (per-block scales)."""

    bits: int = 4
    block: int = 1024

    @property
    def delta(self):  # type: ignore[override]
        levels = (1 << self.bits) - 1
        return max(1e-3, 1.0 - 1.0 / (2 * levels))

    def __call__(self, key, x):
        from repro.kernels.ops import quantize

        return quantize(x, key, bits=self.bits, block=self.block)

    def leaf_wire_bytes(self, size):
        nb = -(-size // self.block)
        return size * self.bits / 8.0 + nb * VALUE_BYTES


_REGISTRY = {
    "identity": lambda **kw: Identity(),
    "topk": lambda **kw: TopK(ratio=kw.get("ratio", 0.2)),
    "block_topk": lambda **kw: BlockTopK(
        ratio=kw.get("ratio", 0.2), block=kw.get("block", 1024)
    ),
    "randk": lambda **kw: RandK(ratio=kw.get("ratio", 0.2)),
    "quant": lambda **kw: StochasticQuant(bits=kw.get("bits", 4)),
    "kernel_topk": lambda **kw: KernelBlockTopK(
        ratio=kw.get("ratio", 0.2), block=kw.get("block", 1024)
    ),
    "kernel_quant": lambda **kw: KernelQuant(
        bits=kw.get("bits", 4), block=kw.get("block", 1024)
    ),
    "lowrank": lambda **kw: LowRank(rank=kw.get("rank", 4)),
}


def make_compressor(name: str, **kwargs) -> Compressor:
    if name not in _REGISTRY:
        raise ValueError(f"unknown compressor {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name](**kwargs)


def empirical_contraction(compressor: Compressor, key: jax.Array, x: jax.Array):
    """Return ||Q(x) - x||^2 / ||x||^2 — must be <= 1 - delta (in expectation
    for randomized Q).  Used by property tests."""
    qx = compressor(key, x)
    num = jnp.sum((qx - x) ** 2)
    den = jnp.maximum(jnp.sum(x**2), 1e-30)
    return num / den
