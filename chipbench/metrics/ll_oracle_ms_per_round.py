"""Device time of the lower-level oracles per round, in ms: the ops under
the program's ``ll_oracle`` scope (`BilevelProblem.grad_y_h` and
`grad_y_g`: the head's forward and gradients in the y and z loops)."""

from chipbench.metrics import ms_per_round

SCOPE = "ll_oracle"


def read(ctx):
    return ms_per_round(ctx, SCOPE)
