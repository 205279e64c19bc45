"""One reader per metric of `BENCHMARK.json`, found by the metric's name:
``read(ctx)`` returns the value; a reader that returns None leaves its
metric out of the result line.  ``ctx`` is built by
`chipbench.harness.run`; per-layer readers run only on a traced run,
whose trace holds the cell's compression kernel (`chipbench.trace_reduce`
stops a run whose kernel pattern matches nothing).

A reader may declare what it reads from the trace: ``SCOPE = "<name>"``,
a named scope of the program (or ``"unscoped"``, the ops under none), or
``KERNEL = "<module of chipbench.kernels>"``.  On a traced run the
harness then splits the device time over them (`chipbench.scopes.read`)
and hands the seconds of each label to every reader as
``ctx.scope_s[<label>]`` (a kernel's under ``ctx.trace["kernel_s"]``
too); ``ctx.scope_s`` is None where the traced program carries no
scopes.
"""


def ms_per_round(ctx, label: str):
    """``label``'s share of the traced device busy time, in ms a round;
    None where the traced program carries no scopes."""
    if ctx.scope_s is None:
        return None
    return 1e3 * ctx.scope_s[label] / ctx.rounds
