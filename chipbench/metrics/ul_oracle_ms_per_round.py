"""Device time of the upper-level oracles per round, in ms: the ops under
the program's ``ul_oracle`` scope (`BilevelProblem.hyper_grad`: the
three x-partials, the backbone's forward and backward)."""

from chipbench.metrics import ms_per_round

SCOPE = "ul_oracle"


def read(ctx):
    return ms_per_round(ctx, SCOPE)
