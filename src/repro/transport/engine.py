"""Transport-generic C2DFB (and baseline) drivers.

`run_c2dfb_transport` is what `c2dfb.run(transport=...)` dispatches to:

* a non-executing transport (`SimTransport`) routes straight back into the
  priced-simulation path with its wrapped fabric — BIT-EXACT with calling
  `run(fabric=...)` directly, including the async engine and topology
  schedules (the committed golden traces pin this);
* an executing transport (`DeviceTransport`) drives the jitted
  `make_device_round` eagerly round-by-round: state and data live sharded
  one node per mesh device, every gossip exchange is a collective, and
  after each round the executed payload stacks make the wire-codec round
  trip (`meter_round`) so ``wire_bytes`` / ``sim_seconds`` are measured on
  real messages.  Metric keys match the synchronous `run` (plus
  ``wall_seconds``) so benchmarks compare backends column-for-column.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bilevel_problem import BilevelProblem
from repro.core.c2dfb import C2DFBState, init_state, outer_round_bytes
from repro.core.topology import Topology
from repro.core.types import (
    Pytree,
    consensus_error,
    node_consensus_dist,
    node_mean,
    tree_sq_norm,
)
from repro.transport.base import Transport
from repro.transport.device import DeviceTransport, make_device_round


def run_c2dfb_transport(
    problem: BilevelProblem,
    topo: Topology,
    cfg,
    x0: Pytree,
    y0: Pytree,
    T: int,
    key: jax.Array,
    transport: Transport,
    jit: bool = True,
    schedule=None,
    async_mode: str | None = None,
    staleness_bound: int = 2,
    version_rule: str = "common",
    ledger=None,
    mixing_damping: str = "none",
    damping_decay: float = 0.5,
    return_payloads: bool = False,
    compiled: bool = False,
    obs=None,
) -> tuple[C2DFBState, dict]:
    """T outer rounds of C2DFB over a `Transport`.  See module docstring;
    ``return_payloads`` additionally stashes the executed per-round inner
    payload stacks in ``metrics["payloads"]`` (device backend only —
    that is what the byte-parity acceptance test audits).  ``obs`` streams
    the shared per-round record (`repro.obs`) from whichever backend runs
    — the SimTransport branch hands it through to `run`, the device loop
    emits ``engine="transport-device"`` rows with executed byte counts.

    Features the device backend does not execute raise
    ``NotImplementedError`` naming the feature (``async_mode``,
    ``compiled``, ``schedule``) so callers can branch on capability with
    one except clause."""
    transport.bind(topo)
    if not transport.executes:
        from repro.core.c2dfb import run

        return run(
            problem, topo, cfg, x0, y0, T, key, jit=jit,
            schedule=schedule, fabric=transport.fabric,
            async_mode=async_mode, staleness_bound=staleness_bound,
            version_rule=version_rule, ledger=ledger,
            mixing_damping=mixing_damping, damping_decay=damping_decay,
            compiled=compiled, obs=obs,
        )

    if async_mode is not None:
        raise NotImplementedError(
            "DeviceTransport does not support async_mode: it executes "
            "synchronous rounds; async needs the priced SimTransport — a "
            "real asynchronous multi-process backend is the ROADMAP "
            "follow-on"
        )
    if version_rule != "common":
        raise NotImplementedError(
            "DeviceTransport executes synchronous rounds: version_rule "
            "selects an ASYNC edge-version protocol — use SimTransport "
            "(or a bare fabric) with async_mode"
        )
    if compiled:
        raise NotImplementedError(
            "DeviceTransport does not support compiled: that is the async "
            "simulator's two-phase scan runtime and the device backend "
            "executes rounds eagerly — use SimTransport (or a bare fabric) "
            "with async_mode for the compiled path"
        )
    if schedule is not None:
        raise NotImplementedError(
            "DeviceTransport does not support schedule: time-varying "
            "topologies are not executed yet — run schedules through "
            "SimTransport (the collective pattern is compiled per graph; "
            "per-round graphs need the follow-on jax.distributed backend)"
        )
    if mixing_damping != "none":
        raise ValueError(
            "mixing_damping is a staleness policy; the device backend is "
            "synchronous (all ages zero) so damping would be a silent no-op"
        )
    assert isinstance(transport, DeviceTransport)
    from repro.obs import as_obs

    obs = as_obs(obs)
    # place every node on its own device BEFORE any compute: the initial
    # oracle calls then run one node per device, not the whole fleet on
    # the default device
    data_f = transport.shard(problem.data_f)
    data_g = transport.shard(problem.data_g)
    state = init_state(
        dataclasses.replace(problem, data_f=data_f, data_g=data_g),
        cfg, transport.shard(x0), transport.shard(y0),
    )
    compressor = cfg.make_compressor()
    fused = transport.fused
    round_fn = make_device_round(
        problem, topo, cfg, transport.mesh, transport.axis, jit=jit,
        fused=fused,
    )
    # one node's inner-residual template: leaf sizes for packed metering
    inner_like = jax.tree.map(
        lambda v: jax.ShapeDtypeStruct(v.shape[1:], v.dtype),
        state.inner_y.d,
    )
    parts = (
        transport.shard(state.x),
        transport.shard(state.s_x),
        transport.shard(state.u_prev),
        transport.shard(state.inner_y),
        transport.shard(state.inner_z),
    )
    m = topo.m
    outer_bytes = outer_round_bytes(state.x)

    keys = jax.random.split(key, T)
    cost = mem0 = fleet_oracles = None
    if obs is not None:
        from repro.obs.compute import (
            c2dfb_oracle_calls,
            memory_peak_bytes,
            round_cost,
        )

        # executed round body's trip-count-aware cost.  shard_map lowers
        # one SPMD module, so the walked FLOPs cover the nodes resident
        # on ONE device (= the whole fleet on the single-device test
        # mesh).  The fused round is a DIFFERENT lowering (pack/unpack
        # matmuls + record-sized collectives), so it gets its own cache
        # key — LM device rows carry its compute_flops/hbm_bytes rather
        # than inheriting the dense round's.
        cost_label = "c2dfb/device-fused" if fused else "c2dfb/device"
        with obs.span("cost_analysis", engine="transport-device"):
            cost = round_cost(
                (
                    cost_label, id(problem), id(topo), cfg,
                    id(transport.mesh), jit, fused, transport.chunk,
                ),
                round_fn,
                *parts, keys[0], data_f, data_g,
                expected_oracles=c2dfb_oracle_calls(cfg),
                label=cost_label,
            )
        fleet_oracles = {
            k: v * m for k, v in c2dfb_oracle_calls(cfg).items()
        }
        mem0 = memory_peak_bytes()
    rows: list[dict] = []
    payload_log: list = []
    for t in range(T):
        x_prev, s_prev = parts[0], parts[1]
        t0 = time.perf_counter()
        x, s_x, u_new, inner_y, inner_z, (q_y, q_z) = round_fn(
            *parts, keys[t], data_f, data_g
        )
        jax.block_until_ready(x)
        wall = time.perf_counter() - t0
        parts = (x, s_x, u_new, inner_y, inner_z)

        t1 = time.perf_counter()
        rep = transport.meter_round(
            [("out/x", x_prev), ("out/s_x", s_prev)],
            [("y", q_y), ("z", q_z)],
            compressor,
            t,
            packed=fused,
            inner_like=inner_like if fused else None,
        )
        meter_wall = time.perf_counter() - t1
        row = {
            "hypergrad_norm": np.sqrt(
                float(tree_sq_norm(node_mean(u_new)))
            ),
            "x_consensus_err": float(consensus_error(x)),
            "sx_consensus_err": float(consensus_error(s_x)),
            "y_consensus_err": float(consensus_error(inner_y.d)),
            "y_compress_err": float(
                tree_sq_norm(
                    jax.tree.map(jnp.subtract, inner_y.d, inner_y.d_hat)
                )
            ),
            "z_consensus_err": float(consensus_error(inner_z.d)),
            # broadcast accounting, same as the simulator's in-scan meter:
            # each inner message counted once per sender (meter_round's
            # executed per-node bytes — codec truth, not a re-count) plus
            # the analytic dense outer term c2dfb_round_core uses
            "measured_bytes": (
                sum(
                    sum(nb)
                    for label, nb in rep["node_bytes"].items()
                    if not label.startswith("out/")
                )
                + outer_bytes
            ),
            "wire_bytes": int(rep["wire_bytes"]),
            "sim_seconds": float(rep["sim_seconds"]),
            "wall_seconds": wall,
            # host wire-metering wall (codec encode/verify of every
            # message) — the round's OTHER cost axis: the fused packed
            # path pays record assembly here instead of host compression
            "meter_seconds": meter_wall,
            "x_node_dist": np.asarray(node_consensus_dist(x)),
        }
        rows.append(row)
        if obs is not None:
            w1 = obs.hostspans.now()
            obs.hostspans.add(f"round[{t}]", w1 - wall, w1)
            # per-stream EXECUTED wire bytes: meter_round prices each
            # sender's message once per directed edge, so a stream's
            # wire share is sum_i deg(i) * node_bytes[i] — the three
            # streams sum to rep["wire_bytes"] exactly, matching the
            # simulator engines' by-stream contract.  Phase labels are
            # "out/x", "out/s_x" and "{y,z}/in{k}/{name}".
            deg = [len(nbrs) for nbrs in topo.neighbors]

            def _stream(prefix):
                return int(
                    sum(
                        sum(d * b for d, b in zip(deg, nb))
                        for label, nb in rep["node_bytes"].items()
                        if label.startswith(prefix)
                    )
                )

            obs.round(
                "transport-device", t, row,
                bytes_by_stream={
                    "outer": _stream("out/"),
                    "y": _stream("y/"),
                    "z": _stream("z/"),
                },
                wall_seconds=wall,
                oracle_calls=fleet_oracles,
                compute_flops=cost.flops,
                hbm_bytes=cost.hbm_bytes,
                compile_seconds=cost.compile_seconds if t == 0 else None,
                memory_peak_bytes=mem0 if t == 0 else None,
            )
            # schema-v2 node rows with EXECUTED codec truth per node:
            # node_bytes counts each message once at its sender (the
            # meter's accounting), the by-stream split sums to it, and
            # deg(i) * node_bytes[i] is node i's wire share — node wire
            # shares sum to the fleet row's wire_bytes exactly (pinned
            # in tests/test_transport.py)
            def _node_stream(prefix, i):
                return int(
                    sum(
                        nb[i]
                        for label, nb in rep["node_bytes"].items()
                        if label.startswith(prefix)
                    )
                )

            x_nd = row["x_node_dist"]
            for i in range(m):
                split = {
                    "outer": _node_stream("out/", i),
                    "y": _node_stream("y/", i),
                    "z": _node_stream("z/", i),
                }
                nbytes = sum(split.values())
                obs.node(
                    "transport-device", t, i,
                    {
                        "x_dist": x_nd[i],
                        "node_bytes": nbytes,
                        "wire_bytes": deg[i] * nbytes,
                        "staleness_max": 0,
                        "staleness_mean": 0.0,
                        "compute_flops": cost.flops / m,
                    },
                    bytes_by_stream=split,
                )
        if return_payloads:
            payload_log.append(
                {
                    "y": jax.tree.map(np.asarray, q_y),
                    "z": jax.tree.map(np.asarray, q_z),
                    "node_bytes": rep["node_bytes"],
                }
            )

    x, s_x, u_new, inner_y, inner_z = parts
    final = C2DFBState(
        x=x, s_x=s_x, u_prev=u_new, inner_y=inner_y, inner_z=inner_z,
        t=state.t + T,
    )
    metrics: dict = {
        k: np.asarray([r[k] for r in rows]) for k in (rows[0] if rows else {})
    }
    if return_payloads:
        metrics["payloads"] = payload_log
    return final, metrics
