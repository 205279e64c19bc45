"""Mamba-2 layer ("mamba"; SSD, arXiv:2405.21060), as
`repro.models.ssm.mamba_apply`: input projection, depthwise causal
convolution of width 4 with SiLU, softplus step with bias, the SSD mixer,
gated RMSNorm, output projection, pre-norm residual; then the SwiGLU MLP
sub-block where ``d_ff > 0`` (`chipbench.kinds._swiglu`), as the program's
`_apply_block` adds it.

The reference computes the mixer in its quadratic "attention" form: for
each head, y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} A dt_r) dt_s x_s
+ D x_t, without chunking.  Its FLOPs are counted as the program's chunked
form does the work: 2 x the projections' parameters x tokens, plus, per
chunk of length Q and head, C.B^T (Q x Q x N), the masked product with X
(Q x Q x P), the carried state's read-out (Q x P x N) and its update
(Q x N x P).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.kinds import _swiglu
from chipbench.reference.norm import rms_norm
from chipbench.weights import Leaf, dense_leaf

D_CONV = 4


def _dims(mc: dict):
    return mc["ssm_heads"], mc["ssm_head_dim"], mc["ssm_state"], mc["ssm_groups"]


def leaves(mc: dict, R: int, p: int) -> dict:
    d, dt = mc["d_model"], mc["dtype"]
    H, P, N, G = _dims(mc)
    di = H * P
    return {
        "norm1": Leaf((R, d), dt, "ones"),
        "mamba": {
            "w_in": dense_leaf(d, 2 * di + 2 * G * N + H, dt, R),
            "w_out": dense_leaf(di, d, dt, R),
            "conv_w": Leaf((R, D_CONV, di + 2 * G * N), dt, "normal", 1.0 / math.sqrt(D_CONV)),
            "a_log": Leaf((R, H), "float32", "log_uniform_1_16"),
            "d_skip": Leaf((R, H), "float32", "ones"),
            "dt_bias": Leaf((R, H), "float32", "zeros"),
            "norm": Leaf((R, di), dt, "ones"),
        },
        **_swiglu.leaves(mc, R, p),
    }


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
    H, P, N, G = _dims(mc)
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
    x = x + mixer(blk["mamba"], mc, rms_norm(x, blk["norm1"], mc["norm_eps"]), mm)
    return _swiglu.apply(blk, mc, x, mm)


def forward_flops(mc: dict, seq: int, tokens: int, p: int) -> float:
    d = mc["d_model"]
    H, P, N, G = _dims(mc)
    di = H * P
    proj = d * (2 * di + 2 * G * N + H) + di * d
    Q = min(mc["ssm_chunk"], seq)
    per_chunk = 2.0 * H * Q * (Q * N + Q * P + 2 * P * N)
    chunks = (tokens // seq) * (seq // Q)
    return 2.0 * (proj + _swiglu.params(mc, p)) * tokens + per_chunk * chunks
