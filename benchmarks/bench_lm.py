"""LM-scale C2DFB executed on devices: fused packed exchange vs host codec.

Runs `make_lm_bilevel` (a real transformer: backbone upper / head lower,
bf16 params) through `DeviceTransport` on 8 virtual devices under TWO
wire-equivalent policies:

    lm_fused   on-device Pallas pack: residuals are compressed AND packed
               to (vals, idx) records inside the shard_map round — the
               collectives move the record form, the dense residual tree
               never exists on the host; metering builds chunked wire
               payloads straight from the records
    lm_host    same math, dense collectives + host-side chunked codec
               compression of every message (the pre-fusion baseline)

The two trajectories are BIT-IDENTICAL (packing is exact value movement
and BlockTopK survivors always fit the record budget) and both meter the
same chunked wire format, so ``wire_bytes`` agree to the byte.  What the
fused path buys is the exchange itself, reported per round:

    wall+meter per round   executed round + wire metering (host codec
                           work is where the baseline pays)
    exchange bytes         analytic packed (nb*kpad*8) vs dense tile
                           (nb*block*4) vs dense bf16 leaf (d*2) message
                           sizes, plus the HLO-measured collective bytes
                           of each lowering (the executed truth)
    roofline               compute/memory/collective seconds from the
                           PR-9 compute meter + `repro.launch.roofline`

The gate block (``BENCH_lm.json``) is ALWAYS the fixed smoke config so a
fresh CI run and the committed baseline price the same problem: wire
bytes / oracle calls / compute FLOPs are exact, per-round wall is banded
(``python -m repro.obs.report RUN.jsonl --gate BENCH_lm.json``).  Hard
claims (SystemExit): byte-identical wire across policies, bit-identical
trajectories, packed < dense exchange bytes both analytically and in the
lowered HLO, fused round+meter beating the host baseline, and a non-None
compute meter on the fused lowering.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python benchmarks/bench_lm.py --smoke
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
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_fn
from repro.configs.base import ModelConfig
from repro.core.c2dfb import C2DFBConfig
from repro.core.c2dfb import run as c2dfb_run
from repro.core.lm_bilevel import init_node_params, make_lm_bilevel
from repro.core.topology import ring
from repro.data.synthetic import node_streams
from repro.transport import DeviceTransport

PROFILE = "wan"
BENCH_PATH = "BENCH_lm.json"

#: the FIXED gate problem — tiny transformer, but every layer of the real
#: path: swiglu blocks, bf16 params, block-top-k head residuals, chunked
#: wire format.  Changing any field invalidates the committed baseline.
GATE = dict(
    m=8, B=2, S=64, T=2, K=3, num_layers=2, d_model=64, num_heads=4,
    num_kv_heads=2, head_dim=16, d_ff=128, vocab=256, block=1024,
    ratio=0.1, chunk=1 << 14, profile=PROFILE, seed=0,
)


def _model_cfg() -> ModelConfig:
    return ModelConfig(
        name="lm-bench", arch_type="dense", pattern=("full",),
        mlp_type="swiglu", num_layers=GATE["num_layers"],
        d_model=GATE["d_model"], num_heads=GATE["num_heads"],
        num_kv_heads=GATE["num_kv_heads"], head_dim=GATE["head_dim"],
        d_ff=GATE["d_ff"], vocab_size=GATE["vocab"],
    )


def _node_data(mcfg: ModelConfig, seed: int):
    streams = node_streams(
        GATE["m"], mcfg.vocab_size, GATE["S"], GATE["B"], seed=seed
    )
    bs = [s.next_batch() for s in streams]
    return {
        "tokens": jnp.asarray(np.stack([b["tokens"] for b in bs])),
        "labels": jnp.asarray(np.stack([b["labels"] for b in bs])),
    }


def _build():
    mcfg = _model_cfg()
    problem = make_lm_bilevel(
        mcfg, _node_data(mcfg, 0), _node_data(mcfg, 1), GATE["m"]
    )
    x0, y0 = init_node_params(
        mcfg, jax.random.PRNGKey(GATE["seed"]), GATE["m"]
    )
    cfg = C2DFBConfig(
        lam=10.0, eta_out=0.02, gamma_out=0.5, eta_in=0.06, gamma_in=0.5,
        K=GATE["K"], compressor="block_topk", comp_ratio=GATE["ratio"],
        comp_block=GATE["block"],
    )
    return problem, ring(GATE["m"]), cfg, x0, y0


def _maxdiff(a, b) -> float:
    return max(
        float(np.max(np.abs(
            np.asarray(x, np.float64) - np.asarray(y, np.float64)
        )))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def exchange_sizes(y0) -> dict:
    """Analytic per-message inner exchange bytes of the three forms the
    head residual can travel in — what the fusion actually changes on the
    interconnect.  ``y0`` is the node-stacked head template; sizes are for
    ONE node's message."""
    from repro.kernels.pack_residuals import padded_k

    block = GATE["block"]
    k = max(1, int(round(GATE["ratio"] * block)))
    kpad = padded_k(k)
    packed = tile = leaf = 0
    for l in jax.tree.leaves(y0):
        d = int(np.prod(np.shape(l)[1:]))
        nb = -(-d // block)
        packed += nb * kpad * 8          # f32 vals + i32 idx records
        tile += nb * block * 4           # padded f32 tile form
        leaf += d * np.dtype(np.asarray(l).dtype).itemsize  # dense leaves
    return {
        "block": block, "k": k, "kpad": kpad,
        "packed_bytes": int(packed),
        "dense_tile_bytes": int(tile),
        "dense_leaf_bytes": int(leaf),
        "packed_over_tile": packed / tile,
        "packed_over_leaf": packed / leaf,
        # y and z loops each broadcast (d, s) per inner step
        "inner_messages_per_round_per_node": 4 * GATE["K"],
    }


def _engine_cost(problem, topo, cfg, transport, fused: bool):
    """The RoundCost the engine memoized for this exact run configuration
    (same key discipline as `run_c2dfb_transport`) — a memo hit, never a
    re-lowering.  SystemExit if the meter failed: the fused SPMD lowering
    carrying its own compute cost is a bench claim, not best-effort."""
    from repro.obs.compute import round_cost

    label = "c2dfb/device-fused" if fused else "c2dfb/device"
    key = (
        label, id(problem), id(topo), cfg, id(transport.mesh), True,
        fused, transport.chunk,
    )
    try:
        return round_cost(key, None)
    except Exception:
        raise SystemExit(
            f"{label}: no memoized RoundCost — the compute meter failed "
            "on this lowering, so compute_flops/hbm_bytes would be None "
            "on LM device rows"
        )


def run_gate(obs=None, merged_trace_path: str | None = None) -> dict:
    """Both policies at the FIXED gate config; returns
    ``(gate_block, extras)`` where extras carries the exchange/roofline/
    per-round evidence for the bench payload."""
    from repro.net import NetTrace
    from repro.launch.roofline import PEAKS, roofline_terms
    from repro.obs import MemorySink, MultiSink, Obs, as_obs, gate_record
    from repro.obs.compute import c2dfb_oracle_calls

    m, T = GATE["m"], GATE["T"]
    if len(jax.devices()) < m:
        emit(
            "lm_gate/skipped", 0.0,
            f"need {m} devices, have {len(jax.devices())}; baseline "
            "not written",
        )
        return {}, {}
    problem, topo, cfg, x0, y0 = _build()
    config = {
        k: GATE[k]
        for k in (
            "m", "B", "S", "T", "K", "num_layers", "d_model", "vocab",
            "block", "ratio", "chunk", "profile", "seed",
        )
    }
    config["compressor"] = "block_topk"
    o = as_obs(obs)
    mem = MemorySink()
    sinks = [s for s in ((o.sink if o is not None else None), mem) if s]
    gate_obs = Obs(
        sink=MultiSink(*sinks),
        run=o.run if o is not None else "bench_lm",
    )
    key = jax.random.PRNGKey(GATE["seed"])
    oc_fleet = {k: v * m for k, v in c2dfb_oracle_calls(cfg).items()}

    block: dict = {"config": config, "policies": {}}
    extras: dict = {"exchange": exchange_sizes(y0), "roofline": {},
                    "rounds": {}}
    merge_trace = None
    states, rounds = {}, {}
    for name, fused in (("lm_fused", True), ("lm_host", False)):
        tr = (
            NetTrace()
            if merged_trace_path is not None and name == "lm_fused"
            else None
        )
        # ONE transport per policy, reused cold+warm: mesh identity keeps
        # the engine's round_cost memoized, so the HLO walk prices each
        # lowering exactly once
        transport = DeviceTransport(
            link=PROFILE, seed=0, fused=fused, chunk=GATE["chunk"],
            trace=tr,
        )
        out = {}

        def call():
            state, mets = c2dfb_run(
                problem, topo, cfg, x0, y0, T=T, key=key,
                transport=transport, obs=gate_obs,
            )
            out["state"], out["mets"] = state, mets
            return mets["y_consensus_err"]

        time_fn(
            call, warmups=0, repeats=1, label=f"lm_gate/{name}/cold",
            obs=gate_obs, engine=name,
        )
        mets = out["mets"]
        wire = int(np.asarray(mets["wire_bytes"]).sum())
        # per-round cost of the whole exchange — executed collectives +
        # host wire metering — from the COLD call's post-compile rounds
        # (round 0 absorbs jit).  This is the first-run experience: the
        # host-codec baseline's data-dependent pack shapes (k = worst-row
        # survivors, different every message) keep re-jitting here, which
        # is an intrinsic cost of host compression; the fused path has
        # one fixed record shape (kpad) and meters from the records.  A
        # verbatim rerun replays the same trajectory (same k sequence),
        # so warm-call meters flatter the baseline — reported in extras,
        # not gated.
        walls = np.asarray(mets["wall_seconds"])
        meters = np.asarray(mets["meter_seconds"])
        round_s = float((walls[1:] + meters[1:]).mean())
        rounds[name] = round_s
        time_fn(
            call, warmups=0, repeats=1, label=f"lm_gate/{name}/warm",
            obs=gate_obs, engine=name,
        )
        wire_warm = int(np.asarray(out["mets"]["wire_bytes"]).sum())
        if wire != wire_warm:
            raise SystemExit(
                f"{name} wire bytes are not deterministic across reruns: "
                f"{wire} vs {wire_warm} — the gate cannot pin them"
            )
        if tr is not None:
            merge_trace = tr
        states[name] = out["state"]
        cost = _engine_cost(problem, topo, cfg, transport, fused)
        if not (cost.flops and cost.flops > 0):
            raise SystemExit(
                f"{name}: compute meter returned flops={cost.flops!r}; "
                "LM device rows must carry non-None compute_flops"
            )
        kind = jax.devices()[0].device_kind
        extras["roofline"][name] = {
            "hlo_collective_bytes": cost.collective_bytes,
            # seconds on this device's published peaks; a device with no
            # published peaks (the CPU) gets none
            **(
                roofline_terms(
                    cost.flops, cost.hbm_bytes, cost.collective_bytes,
                    chips=m, device_kind=kind,
                )
                if kind in PEAKS else {"device_kind": kind}
            ),
        }
        extras["rounds"][name] = {
            "wall_seconds": [float(w) for w in walls],
            "meter_seconds": [float(w) for w in meters],
            "wire_bytes": [int(b) for b in np.asarray(mets["wire_bytes"])],
            "round_plus_meter_s": round_s,
            # verbatim-rerun rounds: same trajectory, so the host codec's
            # data-dependent jit shapes are pre-cached — informational
            "rerun_wall_seconds": [
                float(w) for w in np.asarray(out["mets"]["wall_seconds"])
            ],
            "rerun_meter_seconds": [
                float(w) for w in np.asarray(out["mets"]["meter_seconds"])
            ],
        }
        block["policies"][name] = {
            "wire_bytes": wire,
            "trace_counts": None,
            "warm_wall_s": round_s,
            "oracle_calls": oc_fleet,
            "compute_flops": cost.flops,
            "compile_seconds": cost.compile_seconds,
        }
        gate_obs.emit(gate_record(
            gate_obs.run, name, wire_bytes=wire, trace_counts=None,
            warm_wall_s=round_s, config=config, oracle_calls=oc_fleet,
            compute_flops=cost.flops, compile_seconds=cost.compile_seconds,
        ))
        emit(
            f"lm_gate/{name}",
            round_s * 1e6,
            f"wire_bytes={wire};round_plus_meter_s={round_s:.4f};"
            f"hlo_collective_bytes={int(cost.collective_bytes)}",
        )

    # --- the fused path's hard claims -----------------------------------
    pol = block["policies"]
    if pol["lm_fused"]["wire_bytes"] != pol["lm_host"]["wire_bytes"]:
        raise SystemExit(
            "fused and host-metered wire bytes disagree: "
            f"{pol['lm_fused']['wire_bytes']} vs "
            f"{pol['lm_host']['wire_bytes']} — the packed records are not "
            "byte-equivalent to chunk-encoding the dense tree"
        )
    dx = _maxdiff(states["lm_fused"].x, states["lm_host"].x)
    if dx != 0.0:
        raise SystemExit(
            f"fused vs host trajectories diverged (max|dx|={dx}): "
            "pack/unpack must be exact value movement"
        )
    ex = extras["exchange"]
    if not (
        ex["packed_bytes"] < ex["dense_tile_bytes"]
        and ex["packed_bytes"] < ex["dense_leaf_bytes"]
    ):
        raise SystemExit(
            f"packed records do not shrink the exchange: {ex}"
        )
    coll_f = extras["roofline"]["lm_fused"]["hlo_collective_bytes"]
    coll_h = extras["roofline"]["lm_host"]["hlo_collective_bytes"]
    if not coll_f < coll_h:
        raise SystemExit(
            "fused lowering does not move fewer collective bytes: "
            f"{coll_f} vs {coll_h}"
        )
    if not rounds["lm_fused"] < rounds["lm_host"]:
        raise SystemExit(
            "fused round (exchange + metering) is not faster than the "
            f"host-compression baseline: {rounds['lm_fused']:.4f}s vs "
            f"{rounds['lm_host']:.4f}s"
        )
    emit(
        "lm_gate/claims", 0.0,
        f"trajectory_bit_identical=True;wire_bytes_equal=True;"
        f"packed_over_tile={ex['packed_over_tile']:.3f};"
        f"packed_over_leaf={ex['packed_over_leaf']:.3f};"
        f"hlo_collective_fused_over_host={coll_f / coll_h:.3f};"
        f"round_speedup={rounds['lm_host'] / rounds['lm_fused']:.2f}x",
    )
    if merged_trace_path is not None:
        gate_obs.save_timeline(
            merged_trace_path, merge_trace, node_records=mem.records,
        )
        print(f"# merged perfetto trace: {merged_trace_path}", flush=True)
    return block, extras


def _json_safe(obj):
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
    # harness runs never touch BENCH_lm.json (CLI-only, like the other
    # transport baselines); a fused-only pass is the smoke signal here
    m = GATE["m"]
    if len(jax.devices()) < m:
        emit(
            "lm/skipped", 0.0,
            f"need {m} devices, have {len(jax.devices())}; run "
            "benchmarks/bench_lm.py as a script (it forces CPU virtual "
            "devices) or set XLA_FLAGS",
        )
        return
    problem, topo, cfg, x0, y0 = _build()
    transport = DeviceTransport(
        link=PROFILE, seed=0, fused=True, chunk=GATE["chunk"]
    )
    out = {}

    def call():
        _, mets = c2dfb_run(
            problem, topo, cfg, x0, y0, T=GATE["T"],
            key=jax.random.PRNGKey(GATE["seed"]), transport=transport,
        )
        out["mets"] = mets
        return mets["y_consensus_err"]

    t = time_fn(call, warmups=0, repeats=1, label="lm/fused")
    wire = int(np.asarray(out["mets"]["wire_bytes"]).sum())
    emit("lm/fused", t.best * 1e6 / GATE["T"], f"wire_bytes={wire}")


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="the fixed gate config only (what CI runs)")
    ap.add_argument("--full", action="store_true",
                    help="synonym kept for suite symmetry: the gate "
                         "config IS the bench; flags only tag the meta")
    ap.add_argument("--jsonl", default=None, metavar="PATH",
                    help="stream per-round fleet + per-node records and "
                         "gate rows to this JSONL via repro.obs (`python "
                         "-m repro.obs.report` summarizes and gates)")
    ap.add_argument("--out", default=BENCH_PATH, metavar="PATH",
                    help="where the bench payload is written (default "
                         "BENCH_lm.json; CI writes a scratch path so the "
                         "committed baseline stays the gate reference)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="export the fused run as a merged Perfetto "
                         "timeline (fabric lanes + host spans + per-node "
                         "counter lanes)")
    args = ap.parse_args()
    obs = None
    if args.jsonl:
        from repro.obs import JsonlSink, Obs

        obs = Obs(sink=JsonlSink(args.jsonl), run="bench_lm")
    print("name,us_per_call,derived")
    gate, extras = run_gate(obs=obs, merged_trace_path=args.trace_out)
    if gate:  # skipped (too few devices) -> never clobber the baseline
        payload = {
            "meta": {
                "smoke": args.smoke, "full": args.full,
                "jax": jax.__version__,
                "backend": jax.default_backend(),
            },
            "gate": gate,
            **extras,
        }
        _write_bench_json(payload, args.out)
    if obs is not None:
        obs.close()
        print(f"# obs jsonl: {args.jsonl}", flush=True)


if __name__ == "__main__":
    main()
