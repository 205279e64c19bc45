"""Seconds per C2DFB round: the whole window (from its start to the last
round's ``block_until_ready``) over the rounds completed in it."""


def read(ctx):
    return ctx.window_s / ctx.rounds
