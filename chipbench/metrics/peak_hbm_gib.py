"""The chip's memory peak after the window, in GiB (read before the
reference runs): the allocator's ``peak_bytes_in_use`` (buffers: state,
data, outputs) plus its ``peak_bytes_reserved`` (the compiled programs'
temporaries, which the first does not count)."""


def read(ctx):
    return ctx.peak_bytes / 2**30
