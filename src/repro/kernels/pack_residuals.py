"""Pallas TPU kernels: sparse residual pack / unpack for the wire codec.

``block_topk`` emits dense tiles that are mostly zeros; a real deployment
puts only the survivors on the wire.  These kernels convert between the
dense (nb, block) tile form and the packed (nb, kpad) record form

    vals[b, j] = j-th surviving value of block b         (0.0 past nnz)
    idx[b, j]  = its lane index within the block         (block past nnz)

without any gather/scatter: survivors are ranked by a prefix count over the
keep mask and routed through a one-hot matrix, so both directions are pure
compare + matmul work that the MXU/VPU execute natively (see
/opt/skills/guides/pallas_guide.md — 2D iota, preferred_element_type).

Layout.  Both kernels work on (ROWS, ·) tiles, ROWS = 8 sublanes, so every
block shape obeys Mosaic's (8, 128) rule.  The prefix count is a matmul of
the 0/1 keep mask against an upper-triangular ones matrix, one
``SLAB``-lane slab at a time plus a running carry — exact in float32
(counts stay below 2^24) and small enough for VMEM at any block.  The
one-hot routing is built per row in the orientation that keeps the rank
(pack) or the index (unpack) on the lane axis, so no lane -> sublane
relayout is needed: each row contracts lanes against lanes (an NT matmul)
at HIGHEST precision, which moves float32 values and lane ids exactly.

``kpad`` (k rounded up to the LANE boundary) is the packed row width;
slots past a block's nnz hold the sentinel index ``block`` so unpack and
the serializer drop them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import (
    LANE,
    SLAB,
    ones_upper,
    prefix_count,
    resolve_interpret,
)

ROWS = 8  # sublane-aligned rows per tile

_NT = (((1,), (1,)), ((), ()))  # contract the lane axes of both operands


def padded_k(k: int) -> int:
    return -(-k // LANE) * LANE


def _nt_dot(a, b):
    return jax.lax.dot_general(
        a, b, _NT,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _pack_kernel(x_ref, vals_ref, idx_ref, rank_ref, *, block: int, kpad: int):
    chunk = min(block, SLAB)
    # inclusive prefix count of survivors along each row, slab by slab
    tri = ones_upper(chunk, strict=False)
    carry = jnp.zeros((ROWS, 1), jnp.float32)
    for c0 in range(0, block, chunk):
        keep = (x_ref[:, c0:c0 + chunk] != 0.0).astype(jnp.float32)
        incl = carry + prefix_count(keep, tri)
        # exclusive rank of each survivor among survivors; -1 for dropped
        rank_ref[:, c0:c0 + chunk] = jnp.where(
            keep > 0.0, incl - 1.0, -1.0
        ).astype(jnp.int32)
        carry = carry + jnp.sum(keep, axis=1, keepdims=True)
    nnz = carry.astype(jnp.int32)  # (ROWS, 1)

    slot = jax.lax.broadcasted_iota(jnp.int32, (kpad, chunk), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1).astype(
        jnp.float32
    )

    def row(r, _):
        vals = jnp.zeros((1, kpad), jnp.float32)
        idx = jnp.zeros((1, kpad), jnp.float32)
        for c0 in range(0, block, chunk):
            # route[j, l] = (rank of lane c0 + l == j)
            route = (slot == rank_ref[pl.ds(r, 1), c0:c0 + chunk]).astype(
                jnp.float32
            )
            vals = vals + _nt_dot(x_ref[pl.ds(r, 1), c0:c0 + chunk], route)
            idx = idx + _nt_dot(lane + float(c0), route)
        vals_ref[pl.ds(r, 1), :] = vals
        idx_ref[pl.ds(r, 1), :] = idx.astype(jnp.int32)

    jax.lax.fori_loop(0, ROWS, row, None)
    out_slot = jax.lax.broadcasted_iota(jnp.int32, (ROWS, kpad), 1)
    idx_ref[...] = jnp.where(out_slot < nnz, idx_ref[...], jnp.int32(block))


def _unpack_kernel(vals_ref, idx_ref, o_ref, *, block: int, kpad: int):
    chunk = min(block, SLAB)
    lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, kpad), 0)

    def row(r, _):
        vals = vals_ref[pl.ds(r, 1), :]  # (1, kpad)
        idx = idx_ref[pl.ds(r, 1), :]    # (1, kpad); sentinels route nowhere
        for c0 in range(0, block, chunk):
            # route[l, j] = (record j lands on lane c0 + l)
            route = (lane + c0 == idx).astype(jnp.float32)
            o_ref[pl.ds(r, 1), c0:c0 + chunk] = _nt_dot(vals, route)

    jax.lax.fori_loop(0, ROWS, row, None)


def _pad_rows(a: jnp.ndarray) -> jnp.ndarray:
    pad = (-a.shape[0]) % ROWS
    return jnp.pad(a, ((0, pad), (0, 0))) if pad else a


@functools.partial(jax.jit, static_argnames=("k", "block", "interpret"))
def pack_sparse_blocks(
    x2d: jnp.ndarray, k: int, block: int, interpret: bool | None = None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(nb, block) sparse tiles -> ((nb, kpad) f32 values, (nb, kpad) i32
    local indices).  Requires <= k survivors per row (the top-k contract);
    extra survivors past kpad are dropped by the one-hot routing."""
    interpret = resolve_interpret(interpret)
    nb = x2d.shape[0]
    assert x2d.shape[1] == block and block % LANE == 0, (x2d.shape, block)
    assert block <= SLAB or block % SLAB == 0, (block, SLAB)
    kpad = padded_k(k)
    xp = _pad_rows(x2d.astype(jnp.float32))
    vals, idx = pl.pallas_call(
        functools.partial(_pack_kernel, block=block, kpad=kpad),
        grid=(xp.shape[0] // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((ROWS, kpad), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, kpad), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((xp.shape[0], kpad), jnp.float32),
            jax.ShapeDtypeStruct((xp.shape[0], kpad), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((ROWS, block), jnp.int32)],
        interpret=interpret,
    )(xp)
    return vals[:nb], idx[:nb]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def unpack_sparse_blocks(
    vals: jnp.ndarray,
    idx: jnp.ndarray,
    block: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Inverse of ``pack_sparse_blocks``: scatter records back to dense
    (nb, block) tiles.  Sentinel indices (== block) contribute nothing."""
    interpret = resolve_interpret(interpret)
    nb, kpad = vals.shape
    assert idx.shape == (nb, kpad) and kpad % LANE == 0
    assert block % LANE == 0 and (block <= SLAB or block % SLAB == 0)
    out = pl.pallas_call(
        functools.partial(_unpack_kernel, block=block, kpad=kpad),
        grid=(-(-nb // ROWS),),
        in_specs=[
            pl.BlockSpec((ROWS, kpad), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, kpad), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb + (-nb) % ROWS, block), jnp.float32),
        interpret=interpret,
    )(_pad_rows(vals.astype(jnp.float32)), _pad_rows(idx))
    return out[:nb]
