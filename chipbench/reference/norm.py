"""RMSNorm in float32, as every layer of the reference uses it."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w
