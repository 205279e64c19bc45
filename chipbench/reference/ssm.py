"""Plain Mamba-2 layer (SSD, arXiv:2405.21060 eq. for the selective
state-space duality), in its quadratic "attention" form: for each head,
y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} A dt_r) dt_s x_s + D x_t,
without chunking.  Input projection, depthwise causal convolution of
width 4 with SiLU, softplus step with bias, gated RMSNorm, output
projection.  float32 throughout."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.dense import rms_norm

D_CONV = 4


def segsum(a):
    """a: (..., S) -> (..., S, S) with out[t, s] = sum_{s<r<=t} a_r for
    s <= t and -inf above the diagonal (a masked cumulative sum, not a
    difference of large prefix sums)."""
    S = a.shape[-1]
    x = jnp.broadcast_to(a[..., :, None], a.shape + (S,))  # x[r, s] = a_r
    x = jnp.where(jnp.tril(jnp.ones((S, S), bool), k=-1), x, 0.0)
    out = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((S, S), bool)), out, -jnp.inf)


def mixer(p, mc, h, mm):
    B, S, _ = h.shape
    H, P, N, G = mc["ssm_heads"], mc["ssm_head_dim"], mc["ssm_state"], mc["ssm_groups"]
    di = H * P
    proj = mm("bsd,de->bse", h, p["w_in"])
    z, xbc, dt_raw = proj[..., :di], proj[..., di:2 * di + 2 * G * N], proj[..., 2 * di + 2 * G * N:]
    pad = jnp.pad(xbc, ((0, 0), (D_CONV - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + S] * p["conv_w"][i] for i in range(D_CONV))
    xbc = jax.nn.silu(conv)
    x = xbc[..., :di].reshape(B, S, H, P)
    Bm = jnp.repeat(xbc[..., di:di + G * N].reshape(B, S, G, N), H // G, axis=2)
    Cm = jnp.repeat(xbc[..., di + G * N:].reshape(B, S, G, N), H // G, axis=2)
    dt = jax.nn.softplus(dt_raw + p["dt_bias"])  # (B, S, H)
    A = -jnp.exp(p["a_log"])
    L = jnp.exp(segsum(jnp.swapaxes(A * dt, 1, 2)))  # (B, H, S, S)
    cb = mm("bthn,bshn->bhts", Cm, Bm)
    y = mm("bhts,bshp->bthp", cb * L, x * dt[..., None])
    y = y + x * p["d_skip"][:, None]
    y = y.reshape(B, S, di) * jax.nn.silu(z)
    y = rms_norm(y, p["norm"], 1e-6)
    return mm("bse,ed->bsd", y, p["w_out"])


def layer(blk, mc, x, mm):
    return x + mixer(blk["mamba"], mc, rms_norm(x, blk["norm1"], mc["norm_eps"]), mm)
