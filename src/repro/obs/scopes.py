"""Named scopes on the layers of a C2DFB round.

Each layer boundary of the round body enters ``scope(name)``, a
`jax.named_scope`: the name lands in the compiled program's
``metadata={op_name="..."}`` of every op traced under it, so a device
trace can be split by layer (`chipbench.scopes.scope_times`).
Scopes are metadata only: the compiled instructions are the same with
or without them.

``SCOPES``:

* ``ll_oracle`` — the y and z loops' gradient oracles;
* ``ul_oracle`` — the hypergradient's three x-partials;
* ``exchange``  — W mixing (dense, ``ppermute``, ``all_gather``, delayed);
* ``compress``  — residual, compressor and the reference-point update;
* ``meter``     — the program's own counters: bytes on the wire,
  consensus and compression errors, the hypergradient norm;
* ``update``    — the model and tracker updates (d, s, x, s_x).

Ops outside every scope (scan carry and stacking, key splits, copies)
are read as ``unscoped``.  An oracle scope also bumps its
`repro.obs.compute.record_oracle` site counter, so an oracle site cannot
be counted without being scoped.
"""

from __future__ import annotations

import jax

from repro.obs.compute import record_oracle

SCOPES = ("ll_oracle", "ul_oracle", "exchange", "compress", "meter", "update")

#: oracle scope -> the `record_oracle` kind it counts
ORACLE_SCOPES = {"ll_oracle": "ll_grad", "ul_oracle": "ul_grad"}


def scope(name: str, calls: int = 1):
    """``jax.named_scope(name)`` for one of ``SCOPES``; an oracle scope
    records ``calls`` oracle sites of its kind."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; have {SCOPES}")
    if name in ORACLE_SCOPES:
        record_oracle(ORACLE_SCOPES[name], calls)
    return jax.named_scope(name)
