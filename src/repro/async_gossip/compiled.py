"""Compiled async runtime: precomputed staleness timelines ride ONE
``lax.scan``.

The eager engine (`repro.async_gossip.engine.run_async`) round-trips
through the host every round — it serializes the current residuals for
packet sizes, steps the numpy scheduler, and dispatches a per-round jit —
so at large T the *simulator*, not the math, dominates wall-clock.  This
module splits the run into two phases:

* **Phase 1 (host, once)** — replay the `AsyncScheduler` for all T rounds
  up front (`AsyncScheduler.replay_rounds`) using ANALYTIC payload sizes
  (`engine.analytic_message_bytes`: the compression spec's exact
  steady-state packet size, `wire.measure_tree_bytes` on a dense probe),
  plus the schedule's active-edge masks and re-entry catch-up packets.
  This yields stacked ``(T, K, m, m)`` age tensors, per-round simulated
  seconds / wire bytes, and the cross-round version-lag bookkeeping —
  byte-for-byte the same scheduler calls (and RNG draws) as T eager
  rounds fed the same sizes.

* **Phase 2 (device, once)** — run all T rounds of the SAME round body the
  eager engine jits (`engine.c2dfb_masked_round` /
  `engine.c2dfb_schedule_round`, and the MADSBO/MDBO twin) as a single
  jitted ``lax.scan`` with a donated carry.  The stacked ages ride as scan
  inputs; the zero-age synchronous fast path stays a ``lax.cond`` branch
  inside the one compilation.

The math is IDENTICAL to the eager engine: feed `run_async` the same
analytic sizes (``payload_bytes="analytic"``) and the two trajectories
agree array-for-array (tests/test_compiled_async.py).  What the compiled
path trades is byte accuracy in the *timing model only* — every round is
priced at the steady-state packet size instead of that round's measured
residuals (round 0's residuals are zero, so its measured packets are
header-only; the analytic model charges full size).  The eager engine
stays the byte-accurate reference and the parity oracle.

Ledger and staleness metrics are reconstructed post hoc from the stacked
timelines (`ledger.record_replay` / `ledger.replay_staleness_rows`) —
same records, same curves, one bulk pass instead of T round trips.
"""

from __future__ import annotations

from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np

from repro.async_gossip.engine import (
    _dense_node_bytes,
    _baseline_round_fn,
    _prepare_async_run,
    analytic_message_bytes,
    async_round_cost,
    baseline_masked_round,  # noqa: F401  (re-exported for symmetry)
    baseline_round_cost,
    c2dfb_masked_round,
    c2dfb_schedule_round,
    cached_jit,
    drive_baseline_round,
    record_trace,
    trace_counts,
)
from repro.async_gossip.ledger import (
    StalenessLedger,
    replay_staleness_rows,
)
from repro.async_gossip.scheduler import AsyncScheduler
from repro.core.bilevel_problem import BilevelProblem
from repro.core.c2dfb import (
    C2DFBConfig,
    C2DFBState,
    add_measured_bytes,
    init_state,
)
from repro.core.topology import Topology
from repro.core.types import Pytree, donate_copy


@contextmanager
def _null_span(name, engine=None):
    """Span stand-in when no ``obs`` handle is attached."""
    yield


def run_async_compiled(
    problem: BilevelProblem,
    topo: Topology,
    cfg: C2DFBConfig,
    x0: Pytree,
    y0: Pytree,
    T: int,
    key: jax.Array,
    fabric,
    policy: str = "bounded",
    bound: int = 2,
    version_rule: str = "common",
    ledger: StalenessLedger | None = None,
    scheduler: AsyncScheduler | None = None,
    schedule=None,
    mixing_damping: str = "none",
    damping_decay: float = 0.5,
    fn_cache: dict | None = None,
    donate: bool = True,
    obs=None,
) -> tuple[C2DFBState, dict]:
    """T outer rounds of C2DFB as ONE jitted ``lax.scan`` over
    precomputed staleness timelines — `run_async`'s signature and metric
    contract (keys, dtypes, ledger), reached via
    ``c2dfb.run(async_mode=..., compiled=True)``.

    Payload sizes are always analytic (that is the point: no round's
    timeline may depend on the jitted math).  ``version_rule`` (the
    scheduler's `VERSION_RULES`) is inherited wholesale from the replay:
    the precomputed ages and byte accounting carry the rule, so the
    compiled path is array-for-array equal to the eager engine under
    every rule, acked ack pricing included.  ``fn_cache`` shares the
    scan compilation across runs (`engine.cached_jit`); ``donate=True``
    donates the scan carry so XLA reuses the state buffers in place.

    ``obs`` (a `repro.obs.Obs` or bare sink) streams the same per-round
    records as the eager engine — emitted post hoc after the scan, since
    the math runs as one device program.  For LIVE visibility set
    ``Obs(heartbeat_every=N)``: the scan body emits a heartbeat record
    every N rounds through a jax host callback while the scan is still
    executing.  The callback is an effect, not an op — jit trace counts
    and array-for-array parity with the eager engine are unchanged
    (tests/test_compiled_async.py); the jit cache is keyed on the
    heartbeat config so a heartbeat scan is never reused for a
    heartbeat-free run (or a different handle).
    """
    from repro.async_gossip.mixing import validate_damping
    from repro.net.fabric import edge_list
    from repro.obs import as_obs, scan_heartbeat
    from repro.transport.base import as_transport

    obs = as_obs(obs)
    validate_damping(mixing_damping)
    transport = as_transport(fabric)
    if transport is not None:
        transport.bind(topo)
        fabric = transport.fabric
    scheduler = scheduler or AsyncScheduler(
        transport, policy=policy, bound=bound, version_rule=version_rule
    )
    ledger = ledger if ledger is not None else StalenessLedger()
    state = init_state(problem, cfg, x0, y0)
    comp = cfg.make_compressor()
    outer_node_bytes = _dense_node_bytes(state.x)
    compute_step = (
        fabric.compute_s / (2 * cfg.K + 2) if fabric.compute_s else 0.0
    )
    edges = edge_list(topo)
    plan = _prepare_async_run(scheduler, state, cfg, topo, T, schedule)
    msg_bytes = analytic_message_bytes(state.inner_y, comp)
    span = obs.span if obs is not None else _null_span

    # ---- phase 1: host timeline replay --------------------------------
    with span("replay", engine="async-compiled"):
        rounds = scheduler.replay_rounds(
            T, cfg.K, msg_bytes, msg_bytes, outer_node_bytes, compute_step,
            masks=plan.masks, catchup_bytes=plan.catchup_bytes,
            track_lag=plan.track_lag,
        )
    if not rounds:
        return state, {"ledger": ledger}
    ages_y = jnp.asarray(
        np.stack([rt.tl_y.ages for rt in rounds]), jnp.int32
    )
    ages_z = jnp.asarray(
        np.stack([rt.tl_z.ages for rt in rounds]), jnp.int32
    )
    keys = jax.random.split(key, T)

    # one round body's trip-count-aware cost — the SAME closure + cache
    # key as the eager engine's (no donate/heartbeat components), so the
    # two paths share one analysis and agree exactly; computed BEFORE the
    # carry is donated (lowering is abstract, but the state must exist)
    cost = mem0 = fleet_oracles = None
    if obs is not None:
        from repro.obs.compute import c2dfb_oracle_calls, memory_peak_bytes

        with obs.span("cost_analysis", engine="async-compiled"):
            cost = async_round_cost(
                problem, topo, cfg, plan, mixing_damping, damping_decay,
                state, keys[0],
            )
        fleet_oracles = {
            k: v * topo.m for k, v in c2dfb_oracle_calls(cfg).items()
        }
        mem0 = memory_peak_bytes()

    # ---- phase 2: one scan, donated carry -----------------------------
    cache = fn_cache if fn_cache is not None else {}
    hb = obs is not None and obs.heartbeat_on
    ckey = (
        id(problem), id(topo), cfg, plan.depth, mixing_damping,
        damping_decay, donate,
    ) + (obs.heartbeat_cache_key() if obs is not None else ("hb", 0))
    jit_kw = {"donate_argnums": (0,)} if donate else {}
    if schedule is None:
        def build():
            def body(st, xs):
                t, k, ay, az = xs
                st, mets = c2dfb_masked_round(
                    st, k, ay, az, problem=problem, topo=topo, cfg=cfg,
                    depth=plan.depth, damping=mixing_damping,
                    decay=damping_decay,
                )
                if hb:
                    scan_heartbeat(obs, "async-compiled", t, mets)
                return st, mets

            def scanned(st0, xs):
                record_trace("compiled_scan")
                return jax.lax.scan(body, st0, xs)

            return scanned

        full_key = ("c2dfb/compiled",) + ckey
        scan_label = "scan" if full_key in cache else "compile+scan"
        fn = cached_jit(cache, full_key, build, **jit_kw)
        carry0 = donate_copy(state) if donate else state
        with span(scan_label, engine="async-compiled"):
            state, mets = fn(carry0, (jnp.arange(T), keys, ages_y, ages_z))
            jax.block_until_ready(mets)
    else:
        Ws = jnp.asarray(plan.Ws, jnp.float32)

        def build():
            def body(carry, xs):
                st, hs = carry
                t, k, Wt, ay, az = xs
                st, mets, hs = c2dfb_schedule_round(
                    st, k, Wt, ay, az, hs, problem=problem, topo=topo,
                    cfg=cfg, depth=plan.depth, damping=mixing_damping,
                    decay=damping_decay,
                )
                if hb:
                    scan_heartbeat(obs, "async-compiled", t, mets)
                return (st, hs), mets

            def scanned(carry, xs):
                record_trace("compiled_scan")
                return jax.lax.scan(body, carry, xs)

            return scanned

        full_key = ("c2dfb/compiled-schedule",) + ckey
        scan_label = "scan" if full_key in cache else "compile+scan"
        fn = cached_jit(cache, full_key, build, **jit_kw)
        carry0 = (state, plan.hists)
        if donate:
            carry0 = donate_copy(carry0)
        with span(scan_label, engine="async-compiled"):
            (state, _), mets = fn(
                carry0, (jnp.arange(T), keys, Ws, ages_y, ages_z)
            )
            jax.block_until_ready(mets)

    # ---- phase 3: post-hoc metrics + ledger from the stacked replay ---
    metrics = add_measured_bytes(
        {k: np.asarray(v) for k, v in mets.items()}, state.x
    )
    if plan.masks is not None:
        edges_per_round = [
            tuple((i, j) for i, j in edges if plan.masks[t][i, j])
            for t in range(T)
        ]
    else:
        edges_per_round = [edges] * T
    ledger.record_replay(
        rounds, np.asarray(metrics["x_consensus_err"], np.float64),
        edges_per_round,
    )
    metrics["sim_seconds"] = np.asarray(
        [rt.t_end - rt.t_start for rt in rounds], np.float64
    )
    metrics["wire_bytes"] = np.asarray(
        [
            rt.tl_y.wire_bytes + rt.tl_z.wire_bytes + rt.outer_wire_bytes
            for rt in rounds
        ],
        np.int64,
    )
    smax, smean, shist = replay_staleness_rows(
        rounds, edges_per_round, plan.depth
    )
    metrics["staleness_max"] = smax
    metrics["staleness_mean"] = smean
    metrics["staleness_hist"] = shist
    metrics["ledger"] = ledger
    if obs is not None:
        from repro.async_gossip.ledger import node_staleness_stats

        tc = trace_counts()
        x_nd = np.asarray(metrics["x_node_dist"])
        for t, rt in enumerate(rounds):
            row = {
                k: v[t] for k, v in metrics.items() if k != "ledger"
            }
            obs.round(
                "async-compiled", t, row,
                bytes_by_stream=rt.wire_bytes_by_stream,
                trace_counts=tc,
                oracle_calls=fleet_oracles,
                compute_flops=cost.flops,
                hbm_bytes=cost.hbm_bytes,
                compile_seconds=cost.compile_seconds if t == 0 else None,
                memory_peak_bytes=mem0 if t == 0 else None,
            )
            # schema-v2 node rows from the same replayed timelines the
            # eager engine accounts with — per-node parity by construction
            node_wire = rt.node_wire_bytes
            nmax, nmean = node_staleness_stats(
                (rt.tl_y.ages, rt.tl_z.ages), edges_per_round[t], topo.m
            )
            for i in range(topo.m):
                obs.node(
                    "async-compiled", t, i,
                    {
                        "x_dist": x_nd[t, i],
                        "wire_bytes": node_wire[i],
                        "staleness_max": nmax[i],
                        "staleness_mean": nmean[i],
                        "compute_flops": cost.flops / topo.m,
                    },
                    bytes_by_stream=rt.node_bytes_by_stream(i),
                )
    return state, metrics


def run_baseline_async_compiled(
    alg: str,
    problem: BilevelProblem,
    topo: Topology,
    cfg,
    x0: Pytree,
    y0: Pytree,
    T: int,
    fabric,
    policy: str = "bounded",
    bound: int = 2,
    version_rule: str = "common",
    ledger: StalenessLedger | None = None,
    mixing_damping: str = "none",
    damping_decay: float = 0.5,
    fn_cache: dict | None = None,
    donate: bool = True,
    obs=None,
) -> tuple[object, dict]:
    """MADSBO / MDBO under the async scheduler as one jitted ``lax.scan``
    (reached via ``run_baseline_async(..., compiled=True)``).  Baseline
    packets are dense iterates — their sizes were already analytic — so
    this is trajectory- AND byte-exact with the eager loop, not just
    math-exact.  ``obs`` streams the same per-round records as the eager
    baseline loop (post hoc), plus optional mid-scan heartbeats."""
    from repro.async_gossip.ledger import node_staleness_stats
    from repro.async_gossip.mixing import validate_damping
    from repro.core.baselines import madsbo_init, mdbo_init
    from repro.net.fabric import edge_list
    from repro.obs import as_obs, scan_heartbeat
    from repro.transport.base import as_transport

    if alg not in ("madsbo", "mdbo"):
        raise ValueError(f"unknown async baseline {alg!r}")
    obs = as_obs(obs)
    validate_damping(mixing_damping)
    transport = as_transport(fabric).bind(topo)
    fabric = transport.fabric
    scheduler = AsyncScheduler(
        transport, policy=policy, bound=bound, version_rule=version_rule
    )
    ledger = ledger if ledger is not None else StalenessLedger()
    dy_bytes = _dense_node_bytes(y0)
    dx_bytes = _dense_node_bytes(x0)
    K = cfg.K
    Q = getattr(cfg, "Q", 0)
    N = getattr(cfg, "neumann_N", 0)
    n_units = K + Q + N + 1
    compute_step = fabric.compute_s / n_units if fabric.compute_s else 0.0
    depth = scheduler.depth_for(max(K, Q))
    state = madsbo_init(problem, x0, y0) if alg == "madsbo" else \
        mdbo_init(x0, y0)

    span = obs.span if obs is not None else _null_span

    # ---- phase 1: host timeline replay --------------------------------
    engine_name = "baseline-compiled"
    with span("replay", engine=engine_name):
        rounds = [
            drive_baseline_round(
                scheduler, alg, t, K, Q, N, dy_bytes, dx_bytes, compute_step
            )
            for t in range(T)
        ]
    if not rounds:
        return state, {"ledger": ledger}
    ages_ll = jnp.asarray(
        np.stack([rt.tl_ll.ages for rt in rounds]), jnp.int32
    )
    ages_h = (
        jnp.asarray(np.stack([rt.tl_h.ages for rt in rounds]), jnp.int32)
        if alg == "madsbo" else None
    )

    # one round body's cost, shared closure + key with the eager baseline
    # loop (computed before the carry is donated)
    cost = mem0 = fleet_oracles = None
    if obs is not None:
        from repro.obs.compute import memory_peak_bytes, oracle_calls_for

        with obs.span("cost_analysis", engine=engine_name):
            cost = baseline_round_cost(
                alg, problem, topo, cfg, depth, mixing_damping,
                damping_decay, state,
            )
        fleet_oracles = oracle_calls_for(alg, cfg, m=topo.m)
        mem0 = memory_peak_bytes()

    # ---- phase 2: one scan --------------------------------------------
    cache = fn_cache if fn_cache is not None else {}
    hb = obs is not None and obs.heartbeat_on
    round_fn = _baseline_round_fn(
        cache, alg, problem, topo, cfg, depth, mixing_damping, damping_decay
    )

    def build():
        def body(st, xs):
            t, *rest = xs
            st, mets = round_fn(st, *rest)
            if hb:
                scan_heartbeat(obs, engine_name, t, mets)
            return st, mets

        def scanned(st0, xs):
            record_trace("compiled_scan")
            return jax.lax.scan(body, st0, xs)

        return scanned

    ckey = ("baseline/compiled", alg, id(problem), id(topo), cfg, depth,
            mixing_damping, damping_decay, donate) + (
        obs.heartbeat_cache_key() if obs is not None else ("hb", 0)
    )
    jit_kw = {"donate_argnums": (0,)} if donate else {}
    scan_label = "scan" if ckey in cache else "compile+scan"
    fn = cached_jit(cache, ckey, build, **jit_kw)
    carry0 = donate_copy(state) if donate else state
    ts = jnp.arange(T)
    xs = (ts, ages_ll, ages_h) if alg == "madsbo" else (ts, ages_ll)
    with span(scan_label, engine=engine_name):
        state, mets = fn(carry0, xs)
        jax.block_until_ready(mets)

    # ---- phase 3: post-hoc ledger + metrics ---------------------------
    metrics = {k: np.asarray(v) for k, v in mets.items()}
    x_errs = np.asarray(metrics["x_consensus_err"], np.float64)
    for t, rt in enumerate(rounds):
        ledger.record_loop(t, "ll", rt.tl_ll.ages,
                           rt.tl_ll.start_s(rt.t_start), rt.tl_ll.end_s)
        if rt.tl_h is not None:
            ledger.record_loop(t, "higp", rt.tl_h.ages,
                               rt.tl_h.start_s(rt.tl_ll.end_s),
                               rt.tl_h.end_s)
        ledger.record_point(rt.t_end, float(x_errs[t]))
    metrics["sim_seconds"] = np.asarray(
        [rt.t_end - rt.t_start for rt in rounds], np.float64
    )
    metrics["wire_bytes"] = np.asarray(
        [rt.wire_bytes for rt in rounds], np.int64
    )
    metrics["ledger"] = ledger
    if obs is not None:
        tc = trace_counts()
        edges = edge_list(topo)
        x_nd = np.asarray(metrics["x_node_dist"])
        for t, rt in enumerate(rounds):
            row = {
                k: v[t] for k, v in metrics.items() if k != "ledger"
            }
            obs.round(
                engine_name, t, row,
                bytes_by_stream=rt.wire_bytes_by_stream,
                trace_counts=tc,
                oracle_calls=fleet_oracles,
                compute_flops=cost.flops,
                hbm_bytes=cost.hbm_bytes,
                compile_seconds=cost.compile_seconds if t == 0 else None,
                memory_peak_bytes=mem0 if t == 0 else None,
            )
            # schema-v2 node rows, mirroring the eager baseline loop
            node_wire = rt.node_wire_bytes
            ages_list = (
                (rt.tl_ll.ages,) if rt.tl_h is None
                else (rt.tl_ll.ages, rt.tl_h.ages)
            )
            nmax, nmean = node_staleness_stats(ages_list, edges, topo.m)
            for i in range(topo.m):
                obs.node(
                    engine_name, t, i,
                    {
                        "x_dist": x_nd[t, i],
                        "wire_bytes": node_wire[i],
                        "staleness_max": nmax[i],
                        "staleness_mean": nmean[i],
                        "compute_flops": cost.flops / topo.m,
                    },
                    bytes_by_stream=rt.node_bytes_by_stream(i),
                )
    return state, metrics
