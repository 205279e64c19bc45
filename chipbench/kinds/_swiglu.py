"""The MLP sub-block that the program adds after a block's mixer when
``d_ff > 0`` (`repro.models.transformer._apply_block`), in its SwiGLU form:
x + W_o (silu(W_g h) * W_i h), h = RMSNorm(x).  Shared by the kinds
`chipbench.kinds.full` and `chipbench.kinds.mamba`; a configuration whose
layers carry experts or another MLP type brings a kind module of its own.
"""

from __future__ import annotations

import jax

from chipbench.reference.norm import rms_norm
from chipbench.weights import Leaf, dense_leaf


def d_ff(mc: dict, p: int) -> int:
    """The MLP width of period position ``p`` (0: no MLP); raises where
    the program would put another MLP there."""
    f = mc.get("d_ff", 0)
    if f <= 0:
        return 0
    if mc.get("mlp_type", "swiglu") != "swiglu":
        raise ValueError(f"the SwiGLU sub-block does not cover mlp_type {mc['mlp_type']!r}")
    if mc.get("num_experts", 0) > 0 and p % mc.get("moe_period", 1) == mc.get("moe_offset", 0):
        raise ValueError(f"position {p} holds experts: its kind needs a module of its own")
    return f


def leaves(mc: dict, R: int, p: int) -> dict:
    f = d_ff(mc, p)
    if not f:
        return {}
    d, dt = mc["d_model"], mc["dtype"]
    return {"norm2": Leaf((R, d), dt, "ones"),
            "mlp": {"wi": dense_leaf(d, f, dt, R), "wg": dense_leaf(d, f, dt, R),
                    "wo": dense_leaf(f, d, dt, R)}}


def apply(blk: dict, mc: dict, x, mm):
    """x plus the MLP of ``blk``'s ``norm2`` and ``mlp`` leaves, where it
    has them."""
    if "mlp" not in blk:
        return x
    p = blk["mlp"]
    h = rms_norm(x, blk["norm2"], mc["norm_eps"])
    a = jax.nn.silu(mm("bsd,df->bsf", h, p["wg"])) * mm("bsd,df->bsf", h, p["wi"])
    return x + mm("bsf,fd->bsd", a, p["wo"])


def params(mc: dict, p: int) -> int:
    """The sub-block's matrix parameters (2 x these x tokens are its FLOPs)."""
    return 3 * mc["d_model"] * d_ff(mc, p)
