"""Device time of the gossip exchange per round, in ms: the ops under the
program's ``exchange`` scope (the W mixes of `core/gossip.py`)."""

from chipbench.metrics import ms_per_round

SCOPE = "exchange"


def read(ctx):
    return ms_per_round(ctx, SCOPE)
