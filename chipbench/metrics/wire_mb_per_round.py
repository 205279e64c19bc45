"""Bytes the round's messages carry, in MB (1e6): the mean over the
window's rounds of the program's in-scan inner byte count, plus the dense
outer x and s_x term from the shapes.  The in-scan counter is int32 when
x64 is off: a negative count has wrapped, and the run stops."""


def read(ctx):
    if any(b < 0 for b in ctx.inner_bytes):
        raise RuntimeError(f"inner byte counter wrapped: {ctx.inner_bytes}")
    inner = sum(ctx.inner_bytes) / len(ctx.inner_bytes)
    return (inner + ctx.outer_bytes) / 1e6
