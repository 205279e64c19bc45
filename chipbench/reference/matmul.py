"""Matrix products of the reference, in float32 or (the control) fp8."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def fp8_round(t):
    """Round to float8_e4m3 with one scale for the whole tensor."""
    t = t.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / E4M3_MAX
    return (t / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _f32(eq, a, b):
    return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST, preferred_element_type=jnp.float32)


def _make_fp8(eq: str):
    ins, out = eq.split("->")
    ia, ib = ins.split(",")

    @jax.custom_vjp
    def f(a, b):
        return _f32(eq, fp8_round(a), fp8_round(b))

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        aq, bq, gq = fp8_round(a), fp8_round(b), fp8_round(g)
        da = _f32(f"{out},{ib}->{ia}", gq, bq)
        db = _f32(f"{ia},{out}->{ib}", aq, gq)
        return da, db

    f.defvjp(fwd, bwd)
    return f


class Matmul:
    """``mm(eq, a, b)``: an einsum of two operands in the chosen precision."""

    def __init__(self, precision: str = "f32"):
        if precision not in ("f32", "fp8"):
            raise ValueError(precision)
        self.precision = precision
        self._fp8 = {}

    def __call__(self, eq: str, a, b):
        if self.precision == "f32":
            return _f32(eq, a, b)
        if eq not in self._fp8:
            self._fp8[eq] = _make_fp8(eq)
        return self._fp8[eq](a, b)
