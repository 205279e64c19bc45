"""Public jit'd wrappers over the compression kernels.

Flatten / pad / reshape plumbing lives here; the kernels see clean
(nb, block) tiles.  ``interpret=None`` compiles the kernels with Mosaic
when JAX's default backend is a TPU and runs them in Pallas' interpreter
on any other backend (`repro.kernels.resolve_interpret`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.quantize import quantize_pallas
from repro.kernels.topk_compress import block_topk_pallas


def _to_blocks(x: jnp.ndarray, block: int) -> tuple[jnp.ndarray, int]:
    flat = x.reshape(-1)
    d = flat.shape[0]
    nb = -(-d // block)
    padded = jnp.pad(flat, (0, nb * block - d))
    return padded.reshape(nb, block), d


@functools.partial(jax.jit, static_argnames=("ratio", "block", "interpret"))
def block_topk(
    x: jnp.ndarray, ratio: float = 0.2, block: int = 1024, interpret: bool | None = None
) -> jnp.ndarray:
    """Kernel-backed contractive block top-k compressor (any input shape)."""
    x2d, d = _to_blocks(x, block)
    k = max(1, int(round(ratio * block)))
    out = block_topk_pallas(x2d, k=k, block=block, interpret=interpret)
    return out.reshape(-1)[:d].reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("bits", "block", "interpret"))
def quantize(
    x: jnp.ndarray,
    key: jax.Array,
    bits: int = 4,
    block: int = 1024,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Kernel-backed stochastic quantizer (dequantized output)."""
    x2d, d = _to_blocks(x, block)
    u2d = jax.random.uniform(key, x2d.shape, x2d.dtype)
    out, _ = quantize_pallas(x2d, u2d, bits=bits, block=block, interpret=interpret)
    return out.reshape(-1)[:d].reshape(x.shape)
