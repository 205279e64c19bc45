"""Pure-jnp oracles for the Pallas compression kernels.

These define the EXACT semantics the kernels must reproduce, including
the threshold-bisection selection rule, so kernel tests are
bit-meaningful.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: bisection steps over the int32 bit patterns of non-negative float32
#: magnitudes: they span [0, 2^31), so 31 halvings pin the threshold
BISECT_ITERS = 31


def magnitude_bits(x: jnp.ndarray) -> jnp.ndarray:
    """int32 bit patterns of |x| in float32: ordered exactly as the
    magnitudes are, so integer compares select by magnitude."""
    return jax.lax.bitcast_convert_type(
        jnp.abs(x.astype(jnp.float32)), jnp.int32
    )


def block_topk_ref(x2d: jnp.ndarray, k: int) -> jnp.ndarray:
    """Exact block top-k on a (nb, block) array, by bisection.

    For each row, bisection over the magnitudes' int32 bit patterns finds
    the k-th largest magnitude t exactly (the largest threshold with
    count(|x| >= t) >= k).  Keep every entry with |x| > t, and entries with
    |x| == t in lane order until the row holds exactly k — the selection
    of ``jax.lax.top_k``, which breaks ties toward the lower index.  The
    fixed iteration count makes it deterministic and hardware-friendly
    (compares + reductions + one prefix count, no sort), and a row never
    holds more than k survivors.  Kept entries are returned unchanged in
    the input dtype.
    """
    ab = magnitude_bits(x2d)
    lo = jnp.zeros(ab.shape[:-1] + (1,), jnp.int32)
    hi = jnp.max(ab, axis=-1, keepdims=True) + 1  # count(ab >= hi) == 0

    def body(_, carry):
        lo, hi = carry
        mid = lo + jnp.right_shift(hi - lo, 1)
        take = jnp.sum(ab >= mid, axis=-1, keepdims=True) >= k
        return jnp.where(take, mid, lo), jnp.where(take, hi, mid)

    t, _ = jax.lax.fori_loop(0, BISECT_ITERS, body, (lo, hi))
    room = k - jnp.sum(ab > t, axis=-1, keepdims=True)
    tie = ab == t
    ties_before = jnp.cumsum(tie, axis=-1) - tie  # exclusive, lane order
    keep = (ab > t) | (tie & (ties_before < room))
    return jnp.where(keep, x2d, jnp.zeros_like(x2d))


def quantize_ref(
    x2d: jnp.ndarray, u2d: jnp.ndarray, bits: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row-scaled stochastic uniform quantization.

    u2d are iid U[0,1) samples (same shape as x2d).  Returns the dequantized
    array plus the per-row scales (what a deployment would transmit along
    with the packed codes).  Arithmetic is float32 for every input dtype, as
    in the kernel; both outputs are cast back to the input dtype.
    """
    x = x2d.astype(jnp.float32)
    levels = jnp.float32((1 << bits) - 1)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-12)
    y = x / scale  # [-1, 1]
    steps = (y + 1.0) * 0.5 * levels
    lo = jnp.floor(steps)
    q = lo + (u2d.astype(jnp.float32) < (steps - lo)).astype(jnp.float32)
    deq = (q / levels) * 2.0 - 1.0
    return (deq * scale).astype(x2d.dtype), scale.astype(x2d.dtype)


def pack_ref(x2d: jnp.ndarray, kpad: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Packed record form of (nb, block) sparse tiles: row b's j-th nonzero
    value (in lane order) and its lane index at slot j; slots past the
    row's nonzero count hold 0.0 and the sentinel index ``block``.
    Nonzeros past ``kpad`` in a row are dropped."""
    nb, block = x2d.shape
    keep = x2d != 0
    rank = jnp.cumsum(keep.astype(jnp.int32), axis=1) - 1
    slot = jnp.where(keep, rank, kpad)  # kpad is out of range: dropped
    rows = jnp.arange(nb)[:, None]
    lane = jnp.broadcast_to(jnp.arange(block, dtype=jnp.int32), (nb, block))
    vals = jnp.zeros((nb, kpad), jnp.float32).at[rows, slot].set(
        x2d.astype(jnp.float32), mode="drop"
    )
    idx = jnp.full((nb, kpad), block, jnp.int32).at[rows, slot].set(
        lane, mode="drop"
    )
    return vals, idx


def unpack_ref(vals: jnp.ndarray, idx: jnp.ndarray, block: int) -> jnp.ndarray:
    """Inverse of `pack_ref`: dense (nb, block) f32 tiles; sentinel
    indices (== block) land nowhere."""
    nb = vals.shape[0]
    rows = jnp.arange(nb)[:, None]
    return jnp.zeros((nb, block), jnp.float32).at[rows, idx].set(
        vals.astype(jnp.float32), mode="drop"
    )
