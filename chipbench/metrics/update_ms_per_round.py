"""Device time of the state updates per round, in ms: the ops under the
program's ``update`` scope (the d, s, x and s_x tree-map updates)."""

from chipbench.metrics import ms_per_round

SCOPE = "update"


def read(ctx):
    return ms_per_round(ctx, SCOPE)
