"""The compression kernel's share of its roofline: the least time its
calls in a round need (their bytes from shapes,
`chipbench.kernels.<compressor>.round_bytes`, over the HBM peak; bytes
bound these kernels, which need no MXU FLOPs) over their measured time."""

import importlib


def read(ctx):
    kmod = importlib.import_module(f"chipbench.kernels.{ctx.traffic['compressor']}")
    least = kmod.round_bytes(ctx.head_sizes, ctx.itemsize, ctx.traffic) / (
        ctx.peaks["hbm_bytes_per_s"] * ctx.chips)
    per_round = ctx.trace["kernel_s"]["compress"] / ctx.rounds
    return 100.0 * least / per_round
