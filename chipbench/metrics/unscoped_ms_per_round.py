"""Device time per round, in ms, of the ops under no named scope: the
compiler's slicing, stacking and copies around the scans, key splits."""

from chipbench.metrics import ms_per_round

SCOPE = "unscoped"


def read(ctx):
    return ms_per_round(ctx, SCOPE)
