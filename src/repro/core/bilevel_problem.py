"""Bilevel problem container: per-node UL/LL objectives + derived oracles.

The problem owns node-stacked data shards (heterogeneity lives here) and
exposes exactly the first-order oracles C2DFB needs:

* grad_y_h   : d/dy [ f_i(x_i, y_i) + lam * g_i(x_i, y_i) ]   (inner, for y)
* grad_y_g   : d/dy   g_i(x_i, z_i)                           (inner, for z)
* hyper_grad : u_i = d/dx f_i(x_i,y_i) + lam*(d/dx g_i(x_i,y_i) - d/dx g_i(x_i,z_i))

All oracles are vmapped over the node axis.  Upper/lower variables are
arbitrary pytrees.  ``psi`` (true hyper-objective at the consensus mean) is
available for evaluation/plotting only — algorithms never touch it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.types import Pytree, node_mean
from repro.obs.scopes import scope


@dataclasses.dataclass(frozen=True)
class BilevelProblem:
    """f(x, y, data_f_i) and g(x, y, data_g_i) are per-node scalar losses."""

    f: Callable[[Pytree, Pytree, Pytree], jax.Array]
    g: Callable[[Pytree, Pytree, Pytree], jax.Array]
    data_f: Pytree  # node-stacked validation shards
    data_g: Pytree  # node-stacked training shards
    m: int

    # ---------------- node-stacked oracles --------------------------------
    def grad_y_h(self, lam):
        """Returns grad_fn(y_stacked, x_stacked) for the y inner loop."""

        def h(x, y, df, dg):
            return self.f(x, y, df) + lam * self.g(x, y, dg)

        gy = jax.grad(h, argnums=1)

        def fn(y, x):
            # h = f + lam*g is ONE lower-level gradient oracle per eval
            with scope("ll_oracle"):
                return jax.vmap(gy)(x, y, self.data_f, self.data_g)

        return fn

    def grad_y_g(self):
        gy = jax.grad(self.g, argnums=1)

        def fn(z, x):
            with scope("ll_oracle"):
                return jax.vmap(gy)(x, z, self.data_g)

        return fn

    def hyper_grad(self, x, y, z, lam):
        """u_i per Eq. (4)/(24) — fully first-order hypergradient estimate."""
        with scope("ul_oracle", 3):  # gfx, ggx_y, ggx_z: three x-partials
            gfx = jax.vmap(jax.grad(self.f, argnums=0))(x, y, self.data_f)
            ggx_y = jax.vmap(jax.grad(self.g, argnums=0))(x, y, self.data_g)
            ggx_z = jax.vmap(jax.grad(self.g, argnums=0))(x, z, self.data_g)
            return jax.tree.map(
                lambda a, b, c: a + lam * (b - c), gfx, ggx_y, ggx_z
            )

    # ---------------- evaluation-only helpers -----------------------------
    def mean_f(self, x_bar, y_bar):
        vals = jax.vmap(lambda df: self.f(x_bar, y_bar, df))(self.data_f)
        return jnp.mean(vals)

    def mean_g(self, x_bar, y_bar):
        vals = jax.vmap(lambda dg: self.g(x_bar, y_bar, dg))(self.data_g)
        return jnp.mean(vals)

    def solve_ll(self, x_bar, y0, steps=500, lr=0.1):
        """Gradient-descent LL solve at a consensus x (evaluation only)."""

        def mean_g_loss(y):
            return self.mean_g(x_bar, y)

        def body(y, _):
            return jax.tree.map(
                lambda v, g: v - lr * g, y, jax.grad(mean_g_loss)(y)
            ), None

        y, _ = jax.lax.scan(body, y0, None, length=steps)
        return y

    def psi(self, x_bar, y0, ll_steps=500, ll_lr=0.1):
        """psi(x) = (1/m) sum_i f_i(x, y*(x)) via an inner GD solve."""
        y_star = self.solve_ll(x_bar, y0, ll_steps, ll_lr)
        return self.mean_f(x_bar, y_star)
