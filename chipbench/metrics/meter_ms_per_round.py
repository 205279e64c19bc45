"""Device time of the program's own counters per round, in ms: the ops
under its ``meter`` scope (the in-scan wire bytes and the metrics
dicts)."""

from chipbench.metrics import ms_per_round

SCOPE = "meter"


def read(ctx):
    return ms_per_round(ctx, SCOPE)
