"""Pallas TPU kernel: exact block top-k residual compression by bisection.

Hardware adaptation (DESIGN.md §3): a global magnitude sort is hostile to the
TPU VPU; instead each VMEM-resident block finds its own k-th largest
magnitude by BISECT_ITERS rounds of (compare + reduce) over the magnitudes'
int32 bit patterns — pure elementwise/reduction work that vectorizes, and
exact, since the bit patterns order as the magnitudes do — then masks.
Magnitudes tied at the threshold are kept in lane order up to exactly k
per block (their rank is a prefix count, a matmul against a triangular
ones matrix): the selection of ``jax.lax.top_k``, so a block never carries
more than k survivors — the record budget of the fused packed exchange.
The compressor is contractive with delta = k/block.

The kernel is shape-blocked as (BLOCK_ROWS, block) tiles: grid over row
groups, each tile living in VMEM.  ``block`` is the compression block (one
threshold per row), a multiple of 128 lanes.  ``k`` is static (baked into
the kernel), matching deployment where the compression ratio is a config.
All compares are int32 whatever the input dtype (v5e's VPU has no bf16
compare); kept entries are copied through unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import SLAB, ones_upper, prefix_count, resolve_interpret
from repro.kernels.ref import BISECT_ITERS, magnitude_bits

BLOCK_ROWS = 8  # sublane-aligned rows per tile


def _topk_kernel(x_ref, o_ref, *, k: int, block: int):
    ab = magnitude_bits(x_ref[...])  # (BLOCK_ROWS, block) int32
    lo = jnp.zeros((ab.shape[0], 1), jnp.int32)
    hi = jnp.max(ab, axis=-1, keepdims=True) + 1  # count(ab >= hi) == 0

    def body(_, carry):
        lo, hi = carry
        mid = lo + jnp.right_shift(hi - lo, 1)
        cnt = jnp.sum((ab >= mid).astype(jnp.int32), axis=-1, keepdims=True)
        take = cnt >= k
        return jnp.where(take, mid, lo), jnp.where(take, hi, mid)

    # t: the k-th largest magnitude's bits; room: how many of its ties fit
    t, _ = jax.lax.fori_loop(0, BISECT_ITERS, body, (lo, hi))
    room = k - jnp.sum((ab > t).astype(jnp.float32), axis=-1, keepdims=True)
    chunk = min(block, SLAB)
    tri = ones_upper(chunk, strict=True)
    ties_before = jnp.zeros(t.shape, jnp.float32)
    for c0 in range(0, block, chunk):
        x = x_ref[:, c0:c0 + chunk]
        b = magnitude_bits(x)
        tie = (b == t).astype(jnp.float32)
        rank = ties_before + prefix_count(tie, tri)
        keep = (b > t) | ((tie > 0.0) & (rank < room))
        o_ref[:, c0:c0 + chunk] = jnp.where(keep, x, jnp.zeros_like(x))
        ties_before = ties_before + jnp.sum(tie, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("k", "block", "interpret"))
def block_topk_pallas(
    x2d: jnp.ndarray, k: int, block: int, interpret: bool | None = None
) -> jnp.ndarray:
    """x2d: (nb, block) residual blocks; keeps the k largest magnitudes of
    each row (ties in lane order)."""
    interpret = resolve_interpret(interpret)
    nb = x2d.shape[0]
    assert x2d.shape[1] == block and block % 128 == 0, (x2d.shape, block)
    assert block <= SLAB or block % SLAB == 0, (block, SLAB)
    pad = (-nb) % BLOCK_ROWS
    xp = jnp.pad(x2d, ((0, pad), (0, 0))) if pad else x2d
    grid = (xp.shape[0] // BLOCK_ROWS,)
    out = pl.pallas_call(
        functools.partial(_topk_kernel, k=k, block=block),
        grid=grid,
        in_specs=[pl.BlockSpec((BLOCK_ROWS, block), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((BLOCK_ROWS, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xp.shape, xp.dtype),
        interpret=interpret,
    )(xp)
    return out[:nb] if pad else out
