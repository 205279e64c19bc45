"""Pallas TPU kernels for the compression hot path, with jnp oracles in
``ref.py``.  Every kernel entry point takes ``interpret``: ``None`` compiles
the kernel with Mosaic when JAX's default backend is a TPU and runs Pallas'
interpreter on any other backend (the CPU test path)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

LANE = 128  # TPU lane width
SLAB = 256  # lanes per triangular-matmul prefix-count slab


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret`` as given, or (for ``None``) True unless on a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def ones_upper(n: int, strict: bool) -> jax.Array:
    """(n, n) bf16 ones on and above (or, ``strict``, above) the diagonal,
    the right operand of `prefix_count`."""
    i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return (i < j if strict else i <= j).astype(jnp.bfloat16)


def prefix_count(mask: jax.Array, upper: jax.Array) -> jax.Array:
    """Inclusive (``upper`` from ``ones_upper(strict=False)``) or exclusive
    prefix count of a 0/1 mask along lanes, as f32: a bf16 matmul with an
    f32 accumulator, exact for counts below 2^24, and a form Mosaic lowers
    (it has no cumsum).  Precision is pinned to DEFAULT (one bf16 pass),
    which Mosaic requires for bf16 operands whatever the process default."""
    return jnp.dot(
        mask.astype(jnp.bfloat16), upper,
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32,
    )
