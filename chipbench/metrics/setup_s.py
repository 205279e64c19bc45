"""Process start to the end of set-up: compile or cache load, weights,
data, the initial state and the check rounds."""


def read(ctx):
    return ctx.setup_s
