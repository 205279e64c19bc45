"""Plain C2DFB (arXiv:2410.14115, Algorithms 1 and 2) on the
hyper-representation problem of a cell: upper variable x = backbone
(embedding + blocks), lower variable y = final norm + LM head.

    f_i(x, y) = CE on node i's validation tokens D_f
    g_i(x, y) = CE on node i's training tokens D_g + ridge * |y|^2
    h_i = f_i + lam * g_i

Per outer round, node-stacked (leading axis = node):

    x   <- x + gamma_out * mix(x) - eta_out * s_x
    y   <- IN(grad_y h, ...), z <- IN(grad_y g, ...)      (K steps each)
    u   <- grad_x f(x, y) + lam * (grad_x g(x, y) - grad_x g(x, z))
    s_x <- s_x + gamma_out * mix(s_x) + u - u_prev

and each inner step (reference points d_hat, s_hat; tracker s):

    d     <- d + gamma_in * mix(d_hat) - eta * s
    d_hat <- d_hat + Q(d - d_hat)
    s     <- s + gamma_in * mix(s_hat) + grad(d) - grad_prev
    s_hat <- s_hat + Q(s - s_hat)

with the tracker re-based on the new objective at the start of each
round (s += grad_new(d) - grad_prev).  mix(v)_i = sum_j w_ij (v_j - v_i)
in float32, W the traffic's own gossip matrix
(`chipbench.reference.topology`).  The y loop's step is
eta_in / (1 + lam).  Q is exact block top-k (ties to the lower index).
Gradients and the backbone's hidden states are
float32 (the backbone forward at the round's x is computed once per data
set and reused by the inner loops, since x is fixed through them); the
state is kept in the configuration's dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import kinds, weights
from chipbench.reference.matmul import Matmul
from chipbench.reference.norm import rms_norm


def _f32(tree):
    return jax.tree.map(lambda v: v.astype(jnp.float32), tree)


def _cast_like(tree, like):
    return jax.tree.map(lambda v, w: v.astype(w.dtype), tree, like)


def _node(tree, i):
    return jax.tree.map(lambda v: v[i], tree)


def _stack(trees):
    return jax.tree.map(lambda *v: jnp.stack(v), *trees)


class Reference:
    """The reference run of one cell from a seed.  ``W`` is the gossip
    matrix; ``precision`` "f32" or "fp8" (the control)."""

    def __init__(self, mc: dict, traffic: dict, data_f: dict, data_g: dict,
                 W: np.ndarray, precision: str = "f32"):
        self.mc, self.tr = mc, traffic
        self.m = traffic["nodes"]
        self.df = {k: jnp.asarray(v) for k, v in data_f.items()}
        self.dg = {k: jnp.asarray(v) for k, v in data_g.items()}
        self.Wd = jnp.asarray(W, jnp.float32) - jnp.eye(self.m, dtype=jnp.float32)
        self.mm = Matmul(precision)
        lam, ridge = traffic["lam"], traffic["ridge"]
        mm, layer_fns = self.mm, [mod.layer for mod in kinds.modules(mc)]
        P = len(mc["pattern"])
        R = mc["num_layers"] // P
        eps = mc["norm_eps"]

        def backbone(x, tokens):
            h = x["embed"].astype(jnp.float32)[tokens]
            for r in range(R):
                for p in range(P):
                    blk = jax.tree.map(lambda v: v[r], x["blocks"][p])
                    fn = functools.partial(_apply_layer, layer_fns[p], mc, mm)
                    h = jax.checkpoint(fn)(blk, h)
            return h

        def ce(y, hidden, labels):
            hn = rms_norm(hidden, y["final_norm"], eps)
            logits = mm("bsd,dv->bsv", hn, y["lm_head"])
            lse = jax.nn.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
            return jnp.mean(lse - tgt)

        def ridge_term(y):
            return ridge * sum(jnp.sum(v * v) for v in jax.tree.leaves(y))

        @jax.jit
        def hidden(x, tokens):
            return backbone(_f32(x), tokens)

        @jax.jit
        def grad_y_h(y, hf, lf, hg, lg):
            fn = lambda y: ce(y, hf, lf) + lam * (ce(y, hg, lg) + ridge_term(y))
            return _cast_like(jax.grad(fn)(_f32(y)), y)

        @jax.jit
        def grad_y_g(z, hg, lg):
            fn = lambda z: ce(z, hg, lg) + ridge_term(z)
            return _cast_like(jax.grad(fn)(_f32(z)), z)

        @jax.jit
        def hyper(x, y, z, tf, lf, tg, lg):
            yf, zf = _f32(y), _f32(z)

            def fn(x):
                hf, hg = backbone(x, tf), backbone(x, tg)
                return ce(yf, hf, lf) + lam * (ce(yf, hg, lg) - ce(zf, hg, lg))

            return _cast_like(jax.grad(fn)(_f32(x)), x)

        self._hidden, self._gyh, self._gyg, self._hyper = hidden, grad_y_h, grad_y_g, hyper

    # -- node-stacked oracles -------------------------------------------------
    def hiddens(self, x):
        hf = [self._hidden(_node(x, i), self.df["tokens"][i]) for i in range(self.m)]
        hg = [self._hidden(_node(x, i), self.dg["tokens"][i]) for i in range(self.m)]
        return hf, hg

    def grad_h(self, y, hf, hg):
        return _stack([
            self._gyh(_node(y, i), hf[i], self.df["labels"][i], hg[i],
                      self.dg["labels"][i]) for i in range(self.m)])

    def grad_g(self, z, hg):
        return _stack([self._gyg(_node(z, i), hg[i], self.dg["labels"][i])
                       for i in range(self.m)])

    def hyper_grad(self, x, y, z):
        return _stack([
            self._hyper(_node(x, i), _node(y, i), _node(z, i),
                        self.df["tokens"][i], self.df["labels"][i],
                        self.dg["tokens"][i], self.dg["labels"][i])
            for i in range(self.m)])

    # -- the algorithm ----------------------------------------------------------
    def init(self, seed_key):
        x0, y0 = jax.jit(functools.partial(
            weights.make_xy, self.mc, m=self.m))(seed_key)
        hf, hg = self.hiddens(x0)
        gy, gz = self.grad_h(y0, hf, hg), self.grad_g(y0, hg)
        u0 = self.hyper_grad(x0, y0, y0)
        inner = lambda g: {"d": y0, "d_hat": y0, "s": g, "s_hat": g, "g_prev": g}
        return {"x": x0, "s_x": u0, "u_prev": u0, "y": inner(gy), "z": inner(gz)}

    def round(self, st):
        """One outer round.  Takes the entries out of ``st`` as it goes, so
        the old state is freed as soon as it is used up."""
        tr = self.tr
        x = _outer_x(self.Wd, st.pop("x"), st["s_x"], tr["gamma_out"], tr["eta_out"])
        hf, hg = self.hiddens(x)
        eta_y = tr["eta_in"] / (1.0 + tr["lam"])
        y = self._inner(st.pop("y"), lambda d: self.grad_h(d, hf, hg), eta_y)
        z = self._inner(st.pop("z"), lambda d: self.grad_g(d, hg), tr["eta_in"])
        u = self.hyper_grad(x, y["d"], z["d"])
        s_x = _outer_s(self.Wd, st.pop("s_x"), u, st.pop("u_prev"), tr["gamma_out"])
        return {"x": x, "s_x": s_x, "u_prev": u, "y": y, "z": z}

    def _inner(self, st, grad, eta):
        tr = self.tr
        g = grad(st["d"])
        st = dict(st, s=_add_sub(st["s"], g, st["g_prev"]), g_prev=g)
        for _ in range(tr["K"]):
            d = _inner_d(self.Wd, st["d"], st["d_hat"], st["s"], tr["gamma_in"], eta)
            d_hat = _add(st["d_hat"], self._compress(_sub(d, st["d_hat"])))
            g = grad(d)
            s = _inner_s(self.Wd, st["s"], st["s_hat"], g, st["g_prev"], tr["gamma_in"])
            s_hat = _add(st["s_hat"], self._compress(_sub(s, st["s_hat"])))
            st = {"d": d, "d_hat": d_hat, "s": s, "s_hat": s_hat, "g_prev": g}
        return st

    def _compress(self, tree):
        tr = self.tr
        return jax.tree.map(lambda leaf: jnp.stack([
            _compress_leaf(tr["compressor"], tr["comp_ratio"], tr["comp_block"], leaf[i])
            for i in range(self.m)]), tree)


def _apply_layer(layer_fn, mc, mm, blk, h):
    return layer_fn(blk, mc, h, mm)


def _mix(Wd, v):
    flat = v.reshape(v.shape[0], -1).astype(jnp.float32)
    return (Wd @ flat).reshape(v.shape).astype(v.dtype)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _outer_x(Wd, x, s_x, gamma, eta):
    return jax.tree.map(lambda v, s: v + gamma * _mix(Wd, v) - eta * s, x, s_x)


@functools.partial(jax.jit, static_argnums=(4,))
def _outer_s(Wd, s, u, u_prev, gamma):
    return jax.tree.map(lambda s_, un, up: s_ + gamma * _mix(Wd, s_) + un - up,
                        s, u, u_prev)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _inner_d(Wd, d, d_hat, s, gamma, eta):
    return jax.tree.map(lambda d_, dh, s_: d_ + gamma * _mix(Wd, dh) - eta * s_,
                        d, d_hat, s)


@functools.partial(jax.jit, static_argnums=(5,))
def _inner_s(Wd, s, s_hat, g, g_prev, gamma):
    return jax.tree.map(lambda s_, sh, gn, gp: s_ + gamma * _mix(Wd, sh) + gn - gp,
                        s, s_hat, g, g_prev)


@jax.jit
def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


@jax.jit
def _sub(a, b):
    return jax.tree.map(jnp.subtract, a, b)


@jax.jit
def _add_sub(a, b, c):
    return jax.tree.map(lambda a_, b_, c_: a_ + b_ - c_, a, b, c)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _compress_leaf(kind, ratio, block, v):
    flat = v.reshape(-1)
    d = flat.shape[0]
    nb = -(-d // block)
    x2d = jnp.pad(flat, (0, nb * block - d)).reshape(nb, block)
    if kind == "kernel_topk":
        k = max(1, int(round(ratio * block)))
        _, idx = jax.lax.top_k(jnp.abs(x2d.astype(jnp.float32)), k)
        keep = jnp.zeros(x2d.shape, bool).at[jnp.arange(nb)[:, None], idx].set(True)
        out = jnp.where(keep, x2d, jnp.zeros_like(x2d))
    else:
        raise ValueError(f"the reference has no compressor {kind!r}")
    return out.reshape(-1)[:d].reshape(v.shape)
