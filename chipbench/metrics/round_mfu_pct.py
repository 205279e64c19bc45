"""The round's required FLOPs (`chipbench.flops.round_flops`: 8 F_bb +
(6K + 12) F_head per node) over the traced window's seconds per round,
as a share of the chips' bf16 peak."""

from chipbench.flops import round_flops


def read(ctx):
    s_per_round = ctx.window_s / ctx.rounds
    peak = ctx.peaks["flops_bf16"] * ctx.chips
    return 100.0 * round_flops(ctx.model, ctx.traffic) / s_per_round / peak
