"""Device time of the compression around its kernel per round, in ms: the
ops under the program's ``compress`` scope (`inner_loop.inner_transmit`
and the reference-point adds) other than the kernel itself, which
`compress_kernel_ms_per_round` reads."""

from chipbench.metrics import ms_per_round

SCOPE = "compress"


def read(ctx):
    return ms_per_round(ctx, SCOPE)
