"""Sim vs device transport backends on one C2DFB config.

Runs the IDENTICAL algorithm through both `repro.transport` backends —
`SimTransport` (the priced simulation) and `DeviceTransport` (executed
`shard_map` collectives, one node per device, wire-codec round trip per
message) — and reports, per backend:

    wall_us_per_round    host wall clock (device: real collective execution)
    wire_bytes           per-link bytes.  Sim prices `round_phases`
                         (headerless dense outer + steady-state inner
                         sizes); device counts executed codec encodes of
                         every message — the honest integers differ by
                         the outer DenseCodec headers and per-round nnz,
                         a sub-percent delta (exact per-payload parity
                         with `wire.measure_tree_bytes` is asserted in
                         tests/test_transport.py)
    measured_bytes       the broadcast-accounted inner+outer meter — the
                         SAME accounting in both backends, integer-equal
                         when the trajectories agree
    simulated_seconds    both backends price on the same link model
    final_consensus_err  trajectory agreement check (fp32 tolerance)

Needs one device per node: on CPU the script forces 8 virtual devices
(XLA_FLAGS) when run as a main; under `benchmarks.run` it skips if the
process was started without enough devices.

CLI runs also execute the regression gate (`run_gate`): both backends at
ONE fixed smoke-scale config (m=4, T=3, K=4, ring, wan profile, seed 0)
regardless of flags, so the committed ``BENCH_transport.json`` baseline
and a fresh CI smoke run price the SAME problem.  Executed wire bytes
are exact; warm wall-clock is checked against a generous band
(``python -m repro.obs.report RUN.jsonl --gate BENCH_transport.json``).
``--jsonl`` streams per-round fleet + per-node records and the gate
rows; ``--trace-out`` exports the device run's merged Perfetto timeline
with per-node counter lanes.  Suite-only harness runs (`benchmarks.run`)
never touch the baseline file.

    PYTHONPATH=src python benchmarks/bench_transport.py --smoke
    PYTHONPATH=src python -m benchmarks.run --only transport
"""

from __future__ import annotations

import os
import sys

if __name__ == "__main__":  # force virtual devices BEFORE importing jax
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )

import json

import jax
import numpy as np

from benchmarks.common import emit, time_fn
from repro.core.c2dfb import C2DFBConfig
from repro.core.c2dfb import run as c2dfb_run
from repro.core.topology import ring
from repro.data.bilevel_tasks import coefficient_tuning_task
from repro.net import make_fabric
from repro.transport import DeviceTransport, SimTransport

PROFILE = "wan"
BENCH_PATH = "BENCH_transport.json"

#: the gate's outer-round count — part of the FIXED gate config below
GATE_T = 3


def run_suite(fast: bool = True, smoke: bool = False, obs=None):
    m = 4 if smoke else 8
    if len(jax.devices()) < m:
        emit(
            "transport/skipped", 0.0,
            f"need {m} devices, have {len(jax.devices())}; run "
            "benchmarks/bench_transport.py as a script (it forces CPU "
            "virtual devices) or set XLA_FLAGS",
        )
        return
    T = 3 if smoke else (6 if fast else 20)
    K = 4 if smoke else 8
    bundle = coefficient_tuning_task(
        m=m, n=200 if smoke else 1000, p=30 if smoke else 80, c=5,
        h=0.8, seed=0,
    )
    topo = ring(m)
    cfg = C2DFBConfig(
        lam=10.0, eta_out=0.3, gamma_out=0.5, eta_in=0.3, gamma_in=0.3,
        K=K, compressor="topk", comp_ratio=0.3,
    )
    key = jax.random.PRNGKey(0)

    results = {}
    for name, transport in (
        ("sim", SimTransport(make_fabric(topo, profile=PROFILE, seed=0))),
        ("device", DeviceTransport(link=PROFILE, seed=0)),
    ):
        out = {}

        def call():
            state, mets = c2dfb_run(
                bundle.problem, topo, cfg, bundle.x0, bundle.y0, T=T,
                key=key, transport=transport, obs=obs,
            )
            out["state"], out["mets"] = state, mets
            return mets["y_consensus_err"]

        t = time_fn(
            call, warmups=0, repeats=1, label=f"transport/{name}",
            obs=obs, engine=name,
        )
        mets = out["mets"]
        dt = t.best
        err = float(np.asarray(mets["y_consensus_err"])[-1])
        wire = int(np.asarray(mets["wire_bytes"]).sum())
        sim_s = float(np.asarray(mets["sim_seconds"]).sum())
        results[name] = dict(err=err, wire=wire)
        emit(
            f"transport/{name}",
            dt * 1e6 / T,
            f"wire_bytes={wire};simulated_seconds={sim_s:.2f};"
            f"measured_bytes={int(np.asarray(mets['measured_bytes']).sum())};"
            f"final_consensus_err={err:.5g}",
        )
    # the two backends run the same math: trajectories agree to fp32
    ref, dev = results["sim"]["err"], results["device"]["err"]
    agree = np.isclose(ref, dev, rtol=1e-3, atol=1e-7)
    emit("transport/parity", 0.0,
         f"consensus_err_sim={ref:.6g};consensus_err_device={dev:.6g};"
         f"agree={bool(agree)}")
    return results


def run_gate(obs=None, merged_trace_path: str | None = None) -> dict:
    """The transport regression-gate rows: ALWAYS computed at one FIXED
    smoke-scale config (the ``--smoke`` suite problem: m=4, T=3, K=4,
    ring, wan profile, seed 0) no matter which flags the bench ran with —
    so the committed baseline and a fresh CI smoke run price the SAME
    problem.  Per backend the EXECUTED/priced wire bytes are exact claims
    about the codec and topology (the device side re-runs and asserts the
    count is deterministic); the warm wall-clock (second, jit-warm
    invocation) is only banded by the gate.  ``trace_counts`` is None —
    the transport paths carry no jit trace meter, and
    `repro.obs.report`'s exact check passes None == None.

    Returns the ``"gate"`` block written into ``BENCH_transport.json``
    and emits one ``kind="gate"`` record per backend through ``obs``
    (plus per-round fleet + node rows from the gate runs themselves).
    With ``merged_trace_path`` the device cold run exports the merged
    Perfetto timeline — simulated fabric lanes, host spans, AND the
    schema-v2 per-node counter lanes."""
    from repro.net import NetTrace
    from repro.obs import MemorySink, MultiSink, Obs, as_obs, gate_record

    m, T, K = 4, GATE_T, 4
    if len(jax.devices()) < m:
        emit(
            "transport_gate/skipped", 0.0,
            f"need {m} devices, have {len(jax.devices())}; baseline "
            "not written",
        )
        return {}
    bundle = coefficient_tuning_task(m=m, n=200, p=30, c=5, h=0.8, seed=0)
    topo = ring(m)
    cfg = C2DFBConfig(
        lam=10.0, eta_out=0.3, gamma_out=0.5, eta_in=0.3, gamma_in=0.3,
        K=K, compressor="topk", comp_ratio=0.3,
    )
    config = {
        "m": m, "K": K, "T": T, "n": 200, "p": 30, "topology": "ring",
        "profile": PROFILE, "seed": 0, "compressor": "topk",
        "comp_ratio": 0.3,
    }
    o = as_obs(obs)
    # tee the gate runs' records into memory too: the node rows become
    # the merged trace's per-node counter lanes whatever the caller's
    # sink is (JSONL, socket, or nothing)
    mem = MemorySink()
    sinks = [s for s in ((o.sink if o is not None else None), mem) if s]
    gate_obs = Obs(
        sink=MultiSink(*sinks),
        run=o.run if o is not None else "bench_transport",
    )
    key = jax.random.PRNGKey(0)

    def _transport(name, trace=None):
        if name == "sim":
            return SimTransport(
                make_fabric(topo, profile=PROFILE, seed=0, trace=trace)
            )
        return DeviceTransport(link=PROFILE, seed=0, trace=trace)

    block: dict = {"config": config, "policies": {}}
    merge_trace = None
    for name in ("sim", "device"):
        tr = (
            NetTrace()
            if merged_trace_path is not None and name == "device"
            else None
        )
        out = {}

        def call(transport):
            _, mets = c2dfb_run(
                bundle.problem, topo, cfg, bundle.x0, bundle.y0, T=T,
                key=key, transport=transport, obs=gate_obs,
            )
            out["mets"] = mets
            return mets["y_consensus_err"]

        time_fn(
            lambda: call(_transport(name, trace=tr)), warmups=0, repeats=1,
            label=f"transport_gate/{name}/cold", obs=gate_obs, engine=name,
        )
        wire = int(np.asarray(out["mets"]["wire_bytes"]).sum())
        t_warm = time_fn(
            lambda: call(_transport(name)), warmups=0, repeats=1,
            label=f"transport_gate/{name}/warm", obs=gate_obs, engine=name,
        )
        wire_warm = int(np.asarray(out["mets"]["wire_bytes"]).sum())
        if wire != wire_warm:
            raise SystemExit(
                f"{name} wire bytes are not deterministic across reruns: "
                f"{wire} vs {wire_warm} — the gate cannot pin them"
            )
        if tr is not None:
            merge_trace = tr
        block["policies"][name] = {
            "wire_bytes": wire,
            "trace_counts": None,
            "warm_wall_s": t_warm.best,
        }
        gate_obs.emit(gate_record(
            gate_obs.run, name, wire_bytes=wire, trace_counts=None,
            warm_wall_s=t_warm.best, config=config,
        ))
        emit(
            f"transport_gate/{name}",
            t_warm.best * 1e6 / T,
            f"wire_bytes={wire};warm_wall_s={t_warm.best:.4f}",
        )
    if merged_trace_path is not None:
        gate_obs.save_timeline(
            merged_trace_path, merge_trace, node_records=mem.records,
        )
        print(f"# merged perfetto trace: {merged_trace_path}", flush=True)
    return block


def _json_safe(obj):
    """RFC-8259-safe payload: non-finite floats become None — bare NaN
    tokens would break jq / JSON.parse consumers of the baseline."""
    if isinstance(obj, dict):
        return {k: _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    if isinstance(obj, float) and not np.isfinite(obj):
        return None
    return obj


def _write_bench_json(payload: dict, path: str = BENCH_PATH) -> None:
    with open(path, "w") as fh:
        json.dump(_json_safe(payload), fh, indent=2, sort_keys=True,
                  allow_nan=False)
    print(f"# bench baseline: {path}", flush=True)


def run(fast: bool = True, **_kw):  # benchmarks.run harness entry point
    # no BENCH_transport.json here: the committed baseline comes from the
    # CLI (which always runs the gate); the harness must not clobber it
    run_suite(fast=fast)


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny settings for CI (seconds, not minutes)")
    ap.add_argument("--full", action="store_true", help="larger settings")
    ap.add_argument("--jsonl", default=None, metavar="PATH",
                    help="stream per-round fleet + per-node records (both "
                         "backends) and the timing/gate rows to this JSONL "
                         "via repro.obs — the file `python -m "
                         "repro.obs.report` summarizes and gates")
    ap.add_argument("--out", default=BENCH_PATH, metavar="PATH",
                    help="where the gate payload is written (default "
                         "BENCH_transport.json; CI writes a scratch path "
                         "so the committed baseline stays the gate "
                         "reference)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="export the gate's device run as a merged "
                         "Perfetto timeline (simulated fabric lanes + "
                         "host spans + per-node counter lanes)")
    args = ap.parse_args()
    obs = None
    if args.jsonl:
        from repro.obs import JsonlSink, Obs

        obs = Obs(sink=JsonlSink(args.jsonl), run="bench_transport")
    print("name,us_per_call,derived")
    payload = {
        "meta": {
            "smoke": args.smoke, "full": args.full,
            "jax": jax.__version__, "backend": jax.default_backend(),
        },
        "suite": run_suite(fast=not args.full, smoke=args.smoke, obs=obs),
    }
    # the gate rows are ALWAYS the fixed smoke-scale config (see
    # run_gate) so any two payloads' gate blocks are byte-comparable
    gate = run_gate(obs=obs, merged_trace_path=args.trace_out)
    if gate:  # skipped (too few devices) -> never clobber the baseline
        payload["gate"] = gate
        _write_bench_json(payload, args.out)
    if obs is not None:
        obs.close()
        print(f"# obs jsonl: {args.jsonl}", flush=True)


if __name__ == "__main__":
    main()
