"""Pallas TPU kernel: per-row-scaled stochastic uniform quantization.

One scale per compression block (row); codes are b-bit midpoints.  The
kernel emits the dequantized tensor (what the receiving node reconstructs)
and the per-row scales (what goes on the wire next to the packed codes).
Arithmetic is float32 whatever the input dtype (v5e's VPU has no bf16
compare); both outputs are cast back to the input dtype.

Randomness: U[0,1) samples are passed IN as a tensor, drawn by the caller
with `jax.random`, so the jnp oracle in ref.py sees the same samples and
matches the kernel exactly.  The kernel is compiled with Mosaic on a TPU
and run by Pallas' interpreter elsewhere (`repro.kernels.resolve_interpret`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

BLOCK_ROWS = 8


def _quant_kernel(x_ref, u_ref, o_ref, s_ref, *, bits: int):
    x = x_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    levels = jnp.float32((1 << bits) - 1)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-12)
    y = x / scale
    steps = (y + 1.0) * 0.5 * levels
    lo = jnp.floor(steps)
    q = lo + (u < (steps - lo)).astype(jnp.float32)
    deq = (q / levels) * 2.0 - 1.0
    o_ref[...] = (deq * scale).astype(o_ref.dtype)
    s_ref[...] = jnp.broadcast_to(scale, s_ref.shape).astype(s_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "block", "interpret"))
def quantize_pallas(
    x2d: jnp.ndarray,
    u2d: jnp.ndarray,
    bits: int,
    block: int,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(nb, block) blocks and their U[0,1) samples -> (dequantized (nb,
    block), per-row scales (nb, 1)), both in ``x2d``'s dtype."""
    interpret = resolve_interpret(interpret)
    nb = x2d.shape[0]
    assert x2d.shape[1] == block and block % 128 == 0
    pad = (-nb) % BLOCK_ROWS
    xp = jnp.pad(x2d, ((0, pad), (0, 0)))
    up = jnp.pad(u2d, ((0, pad), (0, 0)))
    grid = (xp.shape[0] // BLOCK_ROWS,)
    out, scales = pl.pallas_call(
        functools.partial(_quant_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, block), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, block), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_ROWS, block), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, 128), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(xp.shape, xp.dtype),
            jax.ShapeDtypeStruct((xp.shape[0], 128), xp.dtype),
        ],
        interpret=interpret,
    )(xp, up)
    return out[:nb], scales[:nb, :1]
