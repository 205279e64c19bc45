"""Paper Figure 3: hyper-representation — reference-point compression (ours)
vs naive error-feedback C2DFB(nc) at identical hyperparameters.

The ``--profile {lan,wan,geo}`` axis prices every round on a simulated
`repro.net` fabric: metrics gain ``simulated_seconds`` / ``wire_bytes``
and a per-round measured-bytes curve (the exact in-scan codec counter),
like `bench_network.py` — so Figure 3's accuracy story and the wire cost
of reaching it come out of one run.

    PYTHONPATH=src python benchmarks/bench_hyperrep.py [--profile wan] [--full]
    PYTHONPATH=src python -m benchmarks.run --only hyperrep
"""

from __future__ import annotations

import os
import sys
import time

import jax
import numpy as np

if __package__ in (None, ""):  # `python benchmarks/bench_hyperrep.py`
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )

from benchmarks.common import emit
from repro.core.baselines import c2dfb_nc_init, c2dfb_nc_round
from repro.core.c2dfb import (
    C2DFBConfig,
    add_measured_bytes,
    c2dfb_round,
    init_state,
    round_phases,
)
from repro.core.topology import ring, two_hop
from repro.core.types import node_mean
from repro.data.bilevel_tasks import hyper_representation_task
from repro.net import make_fabric

#: fabric kwargs per pricing profile (compute_s = local gradient work)
PROFILE_KW = {
    "lan": dict(profile="lan", straggler="none", compute_s=0.01),
    "wan": dict(profile="wan", straggler="none", compute_s=0.01),
    "geo": dict(profile="geo", straggler="lognormal", compute_s=0.01, sigma=0.8),
}


def _curve(vals, n=8) -> str:
    """Compact `a|b|c` curve string (at most n evenly-spaced points)."""
    vals = np.asarray(vals)
    idx = np.linspace(0, len(vals) - 1, min(n, len(vals))).astype(int)
    return "|".join(str(int(v)) for v in vals[idx])


def run(fast: bool = True, profile: str = "wan"):
    m = 10
    T = 12 if fast else 60
    key = jax.random.PRNGKey(0)
    bundle = hyper_representation_task(m=m, n=2000, side=12, hidden=32, h=0.8)
    cfg = C2DFBConfig(lam=10.0, eta_out=0.3, gamma_out=0.3, eta_in=0.5,
                      gamma_in=0.3, K=8, compressor="topk", comp_ratio=0.3)
    for tname, topo in [("ring", ring(m)), ("2hop", two_hop(m))]:
        fabric = make_fabric(topo, seed=0, **PROFILE_KW[profile])
        state = init_state(bundle.problem, cfg, bundle.x0, bundle.y0)
        step = jax.jit(lambda s, k: c2dfb_round(s, k, bundle.problem, topo, cfg))
        k, t0 = key, time.time()
        bytes_curve = []
        for _ in range(T):
            k, kk = jax.random.split(k)
            state, metrics = step(state, kk)
            metrics = add_measured_bytes(metrics, state.x)
            bytes_curve.append(int(metrics["measured_bytes"]))
        dt = time.time() - t0
        # price the trajectory's phases on the fabric (steady-state sizes)
        phases, labels = round_phases(state, cfg, topo, key)
        sim_s, wire_b = 0.0, 0
        for t in range(T):
            rep = fabric.simulate_round(phases, t, labels=labels)
            sim_s += rep["sim_seconds"]
            wire_b += rep["wire_bytes"]
        acc = bundle.test_accuracy(
            node_mean(state.x), node_mean(state.inner_y.d), bundle.predict_fn
        )
        emit(f"fig3/c2dfb/{tname}/{profile}", dt * 1e6 / T,
             f"acc={acc:.3f};comm_mb={sum(bytes_curve)/1e6:.2f};"
             f"wire_bytes={wire_b};simulated_seconds={sim_s:.2f};"
             f"bytes_curve={_curve(bytes_curve)};"
             f"hg={float(metrics['hypergrad_norm']):.4f}")

        nstate = c2dfb_nc_init(bundle.problem, cfg, bundle.x0, bundle.y0)
        nstep = jax.jit(
            lambda s, k: c2dfb_nc_round(s, k, bundle.problem, topo, cfg)
        )
        k, t0 = key, time.time()
        for _ in range(T):
            k, kk = jax.random.split(k)
            nstate, nmetrics = nstep(nstate, kk)
        dt = time.time() - t0
        nacc = bundle.test_accuracy(
            node_mean(nstate.x), node_mean(nstate.inner_y.d), bundle.predict_fn
        )
        nhg = float(nmetrics["hypergrad_norm"])
        stable = np.isfinite(nhg)
        emit(f"fig3/c2dfb_nc/{tname}/{profile}", dt * 1e6 / T,
             f"acc={nacc:.3f};hg={nhg:.4f};stable={stable}")


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", default="wan", choices=sorted(PROFILE_KW),
                    help="network profile the fabric prices the run under")
    ap.add_argument("--full", action="store_true", help="paper-scale settings")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(fast=not args.full, profile=args.profile)


if __name__ == "__main__":
    main()
