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

The kernel is shape-blocked as (rows, block) tiles, one compression block
per row: the grid runs over ``pl.cdiv(nb, rows)`` row tiles, the last one
partial.  ``rows`` comes from `tile_rows`: as many rows as fit the VMEM
budget, so that each bisection step works on many rows at once and the
chains of dependent reductions of different rows overlap.  Every row is
selected on its own, so the rows a partial tile reads past the end never
reach a kept row, and its writes past the end are dropped: no padding
copy around the kernel.  ``block``
is a multiple of 128 lanes; ``k`` is static (baked into the kernel),
matching deployment where the compression ratio is a config.  All
compares are int32 whatever the input dtype (v5e's VPU has no bf16
compare); kept entries are copied through unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import SLAB, ones_upper, prefix_count, resolve_interpret
from repro.kernels.ref import BISECT_ITERS, magnitude_bits

#: VMEM that one tile's live buffers may take (`tile_bytes`): well inside
#: v5e's default scoped VMEM (16 MiB), the rest left to Mosaic's own
#: scratch.  Sets 256 rows for bf16 at block 1024.
VMEM_BUDGET = 6 * 2**20


def tile_bytes(rows: int, block: int, itemsize: int) -> int:
    """Live VMEM of one (rows, block) tile: the input and output tiles,
    each double-buffered, the int32 magnitude bits and one int32
    temporary of the bisection, and eight f32 temporaries of a
    prefix-count slab."""
    return rows * (4 * block * itemsize + 2 * block * 4 + 8 * min(block, SLAB) * 4)


def tile_rows(nb: int, block: int, dtype) -> int:
    """Rows per tile: the most, in whole packed sublane tiles, that fit
    VMEM_BUDGET, and never more than ``nb`` rounded up to 8."""
    itemsize = jnp.dtype(dtype).itemsize
    sub = 8 * max(1, 4 // itemsize)  # rows of one packed (sublane, lane) tile
    fit = VMEM_BUDGET // tile_bytes(1, block, itemsize) // sub * sub
    return min(max(fit, sub), -(-nb // 8) * 8)


def _topk_kernel(x_ref, o_ref, *, k: int, block: int):
    ab = magnitude_bits(x_ref[...])  # (rows, block) int32
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
    nb = x2d.shape[0]
    assert x2d.shape[1] == block and block % 128 == 0, (x2d.shape, block)
    assert block <= SLAB or block % SLAB == 0, (block, SLAB)
    rows = tile_rows(nb, block, x2d.dtype)
    spec = pl.BlockSpec((rows, block), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_topk_kernel, k=k, block=block),
        grid=(pl.cdiv(nb, rows),),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        interpret=resolve_interpret(interpret),
    )(x2d)
