"""One reader per metric of `BENCHMARK.json`, found by the metric's name:
``read(ctx)`` returns the value; a reader that returns None leaves its
metric out of the result line.  ``ctx`` is built by
`chipbench.harness.run`; per-layer readers run only on a traced run,
whose trace holds the cell's compression kernel (`chipbench.trace_reduce`
stops a run whose kernel pattern matches nothing)."""
