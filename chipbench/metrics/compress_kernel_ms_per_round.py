"""Device time of the cell's compression kernel per round, in ms: the
summed durations of its trace events (`chipbench.kernels.<compressor>`
PATTERN) over the traced rounds."""


def read(ctx):
    return 1e3 * ctx.trace["kernel_s"]["compress"] / ctx.rounds
