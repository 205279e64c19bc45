"""Device busy time per round outside the compression kernel, in ms: on
one chip the oracles (forward and backward passes), the elementwise
updates and the dense W mix."""


def read(ctx):
    other = ctx.trace["busy_s"] - ctx.trace["kernel_s"]["compress"]
    return 1e3 * other / ctx.rounds
