"""Algorithm 1 — C2DFB outer loop.

Per outer round t (per node i, node-stacked here):

    x^{t+1}   = x^t + gamma_out * sum_j w_ij (x_j - x_i) - eta_out * (s_x)^t
    y^{t+1}   = IN(h(x^{t+1}, .), y/refs/tracker state, K)      # h = f + lam*g
    z^{t+1}   = IN(g(x^{t+1}, .), z/refs/tracker state, K)
    u^{t+1}   = grad_x f(x,y) + lam * (grad_x g(x,y) - grad_x g(x,z))
    (s_x)^{t+1} = (s_x)^t + gamma_out * mix(s_x) + u^{t+1} - u^t

Outer communications (x and s_x) are uncompressed, matching the paper; all
inner-loop traffic is compressed residuals.  ``round_metrics`` carries the
exact wire bytes so benchmarks reproduce the paper's communication plots.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bilevel_problem import BilevelProblem
from repro.core.compression import Compressor, make_compressor
from repro.core.gossip import mix_delta_dense
from repro.core.inner_loop import (
    InnerState,
    inner_init,
    inner_loop,
    inner_message_bytes,
    inner_round_phases,
    inner_wire_bytes_per_round,
    refresh_tracker,
)
from repro.core.topology import Topology
from repro.core.types import (
    Pytree,
    consensus_error,
    donate_copy,
    node_consensus_dist,
    node_mean,
    tree_count,
    tree_sq_norm,
)
from repro.obs.scopes import scope


@dataclasses.dataclass(frozen=True)
class C2DFBConfig:
    lam: float = 10.0
    eta_out: float = 0.5
    gamma_out: float = 0.5
    eta_in: float = 0.1
    gamma_in: float = 0.5
    K: int = 10
    compressor: str = "topk"
    comp_ratio: float = 0.2
    comp_bits: int = 4
    comp_block: int = 1024
    # Theorem 1 prescribes eta_in ~ 1/(kappa * lam * L_g) for the y-loop whose
    # objective h = f + lam*g is (1+lam)L-smooth.  We expose eta_in as the
    # z-loop (plain g) step and scale the y-loop step by 1/(1+lam) so a single
    # knob stays stable across lambda; set scale_eta_y=False to disable.
    scale_eta_y: bool = True

    @property
    def eta_in_y(self) -> float:
        return self.eta_in / (1.0 + self.lam) if self.scale_eta_y else self.eta_in

    def make_compressor(self) -> Compressor:
        return make_compressor(
            self.compressor,
            ratio=self.comp_ratio,
            bits=self.comp_bits,
            block=self.comp_block,
        )


class C2DFBState(NamedTuple):
    x: Pytree          # node-stacked UL models
    s_x: Pytree        # node-stacked UL gradient trackers
    u_prev: Pytree     # previous hypergradient estimates
    inner_y: InnerState
    inner_z: InnerState
    t: jax.Array


def init_state(
    problem: BilevelProblem, cfg: C2DFBConfig, x0: Pytree, y0: Pytree
) -> C2DFBState:
    """x0/y0 are node-stacked initial points; z0 = y0 (Algorithm 1)."""
    grad_h = problem.grad_y_h(cfg.lam)
    grad_g = problem.grad_y_g()
    inner_y = inner_init(y0, lambda d: grad_h(d, x0))
    inner_z = inner_init(y0, lambda d: grad_g(d, x0))
    u0 = problem.hyper_grad(x0, y0, y0, cfg.lam)
    return C2DFBState(
        x=x0, s_x=u0, u_prev=u0, inner_y=inner_y, inner_z=inner_z, t=jnp.array(0)
    )


def c2dfb_round_core(
    state: C2DFBState,
    key: jax.Array,
    problem: BilevelProblem,
    W: jax.Array,
    cfg: C2DFBConfig,
    inner_fn,
) -> tuple[C2DFBState, dict]:
    """Shared outer-round body (Algorithm 1).  ``inner_fn(inner_state, key,
    grad_fn, eta, tag)`` runs one K-step inner loop and returns
    ``(state, metrics)`` — the synchronous path plugs in `inner_loop`, the
    async engine (`repro.async_gossip`) a staleness-gated runner keyed by
    ``tag`` ("y" / "z")."""
    ky, kz = jax.random.split(key)

    # ---- outer model update (uncompressed gossip + tracked descent) -------
    mix_x = mix_delta_dense(W, state.x)
    with scope("update"):
        x_new = jax.tree.map(
            lambda x, mx, s: x + cfg.gamma_out * mx - cfg.eta_out * s,
            state.x,
            mix_x,
            state.s_x,
        )

    # ---- inner loops on the new x -----------------------------------------
    grad_h = problem.grad_y_h(cfg.lam)
    grad_g = problem.grad_y_g()
    gy = lambda d: grad_h(d, x_new)
    gz = lambda d: grad_g(d, x_new)

    inner_y = refresh_tracker(state.inner_y, gy)
    inner_z = refresh_tracker(state.inner_z, gz)
    inner_y, my = inner_fn(inner_y, ky, gy, cfg.eta_in_y, "y")
    inner_z, mz = inner_fn(inner_z, kz, gz, cfg.eta_in, "z")

    # ---- hypergradient + tracker update ------------------------------------
    u_new = problem.hyper_grad(x_new, inner_y.d, inner_z.d, cfg.lam)
    mix_s = mix_delta_dense(W, state.s_x)
    with scope("update"):
        s_x_new = jax.tree.map(
            lambda s, ms, un, up: s + cfg.gamma_out * ms + un - up,
            state.s_x,
            mix_s,
            u_new,
            state.u_prev,
        )

    new_state = C2DFBState(
        x=x_new,
        s_x=s_x_new,
        u_prev=u_new,
        inner_y=inner_y,
        inner_z=inner_z,
        t=state.t + 1,
    )
    # inner message bytes, counted inside the scan by the jit nnz/byte
    # counter on the actual payloads; `add_measured_bytes` adds the static
    # outer term on the host
    with scope("meter"):
        metrics = {
            "hypergrad_norm": jnp.sqrt(tree_sq_norm(node_mean(u_new))),
            "x_consensus_err": consensus_error(x_new),
            "sx_consensus_err": consensus_error(s_x_new),
            "y_consensus_err": my["consensus_err"],
            "y_compress_err": my["compress_err"],
            "z_consensus_err": mz["consensus_err"],
            "inner_bytes": my["msg_bytes"] + mz["msg_bytes"],
            # per-node consensus distance (m,): sum of squares ==
            # x_consensus_err; the obs layer's schema-v2 node rows report
            # it, round records skip it
            "x_node_dist": node_consensus_dist(x_new),
        }
    return new_state, metrics


def outer_round_bytes(x: Pytree) -> int:
    """Dense outer wire bytes of one round (broadcast accounting): x and
    s_x as f32, each sent once per node.  ``x`` is node-stacked."""
    m = jax.tree.leaves(x)[0].shape[0]
    return 2 * tree_count(x) * 4 * m


def add_measured_bytes(metrics: dict, x: Pytree) -> dict:
    """Host side of the byte meter: replaces the round body's traced
    ``inner_bytes`` with ``measured_bytes`` = inner + `outer_round_bytes`,
    summed in int64 (exact at LM widths, where the outer term alone
    passes the int32 range of the traced counter)."""
    out = dict(metrics)
    inner = np.asarray(out.pop("inner_bytes"), np.int64)
    out["measured_bytes"] = inner + outer_round_bytes(x)
    return out


def c2dfb_round(
    state: C2DFBState,
    key: jax.Array,
    problem: BilevelProblem,
    topo: Topology,
    cfg: C2DFBConfig,
    W: jax.Array | None = None,
    fabric=None,
    round_idx: int = 0,
    transport=None,
) -> tuple[C2DFBState, dict]:
    """One outer round.  ``W`` overrides the static mixing matrix (used by
    `repro.net.dynamic` schedules — pass the round's matrix, possibly a
    traced scan input).  ``fabric`` (a `repro.net.fabric.NetworkFabric`,
    eager mode only) adds codec-measured ``wire_bytes`` and simulated
    ``sim_seconds`` to the round metrics.  ``transport`` (a
    `repro.transport.Transport`) does the same through the transport's
    pricing face — its fabric-mirroring API makes the two code paths one;
    for a fully EXECUTED round use `run(transport=...)` instead.

    The round's inner message bytes come back as the traced
    ``inner_bytes``; `add_measured_bytes` makes them ``measured_bytes``."""
    W_override = W
    if transport is not None:
        if fabric is not None:
            raise ValueError("pass fabric OR transport, not both")
        fabric = transport.bind(topo)
    W = jnp.asarray(topo.W if W is None else W, dtype=jnp.float32)
    compressor = cfg.make_compressor()

    def inner_fn(st, k, grad_fn, eta, tag):
        return inner_loop(
            st, k, grad_fn, W, compressor, cfg.gamma_in, eta, cfg.K
        )

    new_state, metrics = c2dfb_round_core(state, key, problem, W, cfg, inner_fn)
    if fabric is not None:
        from repro.net.fabric import edges_from_weights, mask_phases

        phases, labels = round_phases(new_state, cfg, fabric.topo, key)
        if W_override is not None:
            # a schedule's W override deactivates links; don't price them
            phases = mask_phases(phases, edges_from_weights(W_override))
        rep = fabric.simulate_round(phases, round_idx, labels=labels)
        metrics["wire_bytes"] = rep["wire_bytes"]
        metrics["sim_seconds"] = rep["sim_seconds"]
    return new_state, metrics


def round_phases(
    state: C2DFBState, cfg: C2DFBConfig, topo: Topology, key: jax.Array
) -> tuple[list, list]:
    """One outer round as a sequence of barrier phases with per-edge byte
    payloads: 2 uncompressed broadcasts (x, s_x) + 2 inner loops x K steps
    x 2 codec-measured compressed messages."""
    from repro.net.fabric import edge_list

    edges = edge_list(topo)
    dx = tree_count(state.x)
    dense = {e: dx * 4 for e in edges}
    phases, labels = [dense, dense], ["out/x", "out/s_x"]
    comp = cfg.make_compressor()
    ky, kz = jax.random.split(jax.random.fold_in(key, 0x5EED))
    for name, inner, k_ in (("y", state.inner_y, ky), ("z", state.inner_z, kz)):
        ph, lb = inner_round_phases(inner, comp, topo, k_, cfg.K)
        phases += ph
        labels += [f"{name}/{s}" for s in lb]
    return phases, labels


def round_wire_bytes_measured(
    state: C2DFBState, cfg: C2DFBConfig, topo: Topology, key: jax.Array
) -> dict:
    """Exact integer bytes per outer round, serialized by the wire codec
    (`repro.net.wire`) instead of the analytic `round_wire_bytes` estimate.
    Outer x/s_x broadcasts are dense f32; inner messages are measured on the
    current reference-point residuals."""
    from repro.net.wire import codec_for

    m = topo.m
    comp = cfg.make_compressor()
    dense = codec_for(make_compressor("identity"))
    # one x broadcast + one s_x broadcast per node, dense f32 (as the paper)
    one_x = jax.tree.map(lambda v: v[0], state.x)
    one_s = jax.tree.map(lambda v: v[0], state.s_x)
    outer = (dense.tree_bytes(one_x) + dense.tree_bytes(one_s)) * m
    ky, kz = jax.random.split(key)
    inner = 0
    for st, k_ in ((state.inner_y, ky), (state.inner_z, kz)):
        bd, bs = inner_message_bytes(st, comp, k_)
        inner += (sum(bd) + sum(bs)) * cfg.K
    return {
        "outer_bytes": outer,
        "inner_bytes": inner,
        "total_bytes": outer + inner,
    }


def round_wire_bytes(
    state: C2DFBState, cfg: C2DFBConfig, topo: Topology
) -> dict:
    """Exact bytes per outer round (all nodes): uncompressed x + s_x
    broadcasts, plus 2 inner loops x K steps x 2 compressed messages."""
    m = topo.m
    one_x = jax.tree.map(lambda v: v[0], state.x)
    one_y = jax.tree.map(lambda v: v[0], state.inner_y.d)
    one_z = jax.tree.map(lambda v: v[0], state.inner_z.d)
    comp = cfg.make_compressor()
    dx = tree_count(state.x)
    outer = 2.0 * dx * 4 * m  # x and s_x, fp32
    inner = inner_wire_bytes_per_round(comp, one_y, cfg.K, m)
    inner += inner_wire_bytes_per_round(comp, one_z, cfg.K, m)
    return {"outer_bytes": outer, "inner_bytes": inner, "total_bytes": outer + inner}


def run(
    problem: BilevelProblem,
    topo: Topology,
    cfg: C2DFBConfig,
    x0: Pytree,
    y0: Pytree,
    T: int,
    key: jax.Array,
    jit: bool = True,
    schedule=None,
    fabric=None,
    async_mode: str | None = None,
    staleness_bound: int = 2,
    version_rule: str = "common",
    ledger=None,
    mixing_damping: str = "none",
    damping_decay: float = 0.5,
    transport=None,
    compiled: bool = False,
    obs=None,
) -> tuple[C2DFBState, dict]:
    """Run T outer rounds under lax.scan; returns final state + stacked metrics.

    ``schedule`` (a `repro.net.dynamic.TopologySchedule`) swaps the static W
    for the schedule's per-round matrices — they ride through the scan as a
    stacked (T, m, m) input, so the loop stays jitted.  ``fabric`` (a
    `repro.net.fabric.NetworkFabric`) appends a simulated wall-clock
    timeline: metrics gain ``sim_seconds`` and ``wire_bytes`` arrays of
    length T (payload sizes codec-measured on the final state's residuals,
    representative of steady state; the fabric's stragglers/jitter still
    vary per round).  Metrics always carry ``measured_bytes`` — the exact
    per-round byte curve (int64): inner messages counted inside the scan,
    plus the static dense outer term (`add_measured_bytes`).

    ``async_mode`` switches to the event-driven asynchronous engine
    (`repro.async_gossip`): "sync" (per-step global barriers, the reference
    timing), "bounded" (nodes run ahead up to ``staleness_bound`` inner
    steps), or "full" (never wait; mix whatever reference points have
    arrived).  Requires ``fabric``; ``ledger`` (a
    `repro.async_gossip.StalenessLedger`) records per-edge staleness.
    ``async_mode`` COMPOSES with ``schedule``: each round runs on the
    schedule's active edge set, dropped edges freeze their reference
    history and re-enter with their true version age (see
    `repro.async_gossip.engine.run_async`).  ``mixing_damping`` damps each
    edge's weight by its current staleness ("none" / "inverse-age" /
    "exp-decay", async modes only) — inverse-age keeps the fully-async
    policy contractive at mixing steps where undamped delayed gossip
    diverges.

    ``version_rule`` (async modes only) selects the edge-version protocol
    (`repro.async_gossip.VERSION_RULES`): the idealized ``"common"``
    default, the realizable ``"deterministic"`` k - S rule, or the
    ``"acked"`` rule pricing sequence-number acks on the wire.

    ``transport`` (a `repro.transport.Transport`) selects the backend the
    round's gossip runs on: `SimTransport` is the priced simulation (this
    function with ``fabric=transport.fabric`` — bit-exact, golden-trace
    pinned), `DeviceTransport` EXECUTES every exchange as `shard_map`
    collectives over a device mesh carrying the real wire-codec payloads.
    Mutually exclusive with ``fabric``.

    ``compiled`` (async modes only) switches to the two-phase compiled
    runtime (`repro.async_gossip.compiled`): the scheduler is replayed
    once on the host with analytic payload sizes and all T rounds ride a
    single jitted ``lax.scan`` with a donated carry — same math as the
    eager engine (parity-tested array-for-array), byte accuracy traded
    only in the timing model.  Use it for large T / LM-scale trees where
    the eager engine's per-round host round-trips dominate wall-clock;
    keep the default eager engine when per-round codec-measured packet
    sizes matter.

    ``obs`` (a `repro.obs.Obs`, or any object with an ``emit(record)``
    method) is the ONE telemetry surface every execution path shares:
    each round streams a schema-stable record (`repro.obs.records`) —
    errors, bytes by stream, staleness, simulated and wall seconds — to
    the attached sink, whichever engine actually runs."""
    if transport is not None:
        if fabric is not None:
            raise ValueError(
                "pass fabric OR transport, not both — a transport owns its "
                "pricing fabric"
            )
        from repro.transport.engine import run_c2dfb_transport

        return run_c2dfb_transport(
            problem, topo, cfg, x0, y0, T, key, transport, jit=jit,
            schedule=schedule, async_mode=async_mode,
            staleness_bound=staleness_bound, version_rule=version_rule,
            ledger=ledger, mixing_damping=mixing_damping,
            damping_decay=damping_decay, compiled=compiled, obs=obs,
        )
    if async_mode is not None:
        if fabric is None:
            raise ValueError("async_mode requires a NetworkFabric")
        if compiled:
            from repro.async_gossip.compiled import run_async_compiled

            return run_async_compiled(
                problem, topo, cfg, x0, y0, T, key, fabric,
                policy=async_mode, bound=staleness_bound,
                version_rule=version_rule, ledger=ledger,
                schedule=schedule, mixing_damping=mixing_damping,
                damping_decay=damping_decay, obs=obs,
            )
        from repro.async_gossip.engine import run_async

        return run_async(
            problem, topo, cfg, x0, y0, T, key, fabric,
            policy=async_mode, bound=staleness_bound,
            version_rule=version_rule, ledger=ledger,
            schedule=schedule, mixing_damping=mixing_damping,
            damping_decay=damping_decay, obs=obs,
        )
    if compiled:
        raise ValueError(
            "compiled=True is the ASYNC runtime's two-phase scan; the "
            "synchronous path already runs as one jitted lax.scan — drop "
            'compiled, or pass async_mode="sync"/"bounded"/"full" (with a '
            "fabric) to run the compiled async engine"
        )
    if version_rule != "common":
        raise ValueError(
            "version_rule is an async protocol choice: the synchronous "
            "path has no versions to agree on — pass async_mode="
            '"sync"/"bounded"/"full" (with a fabric) to select '
            "'deterministic' or 'acked' timelines"
        )
    if mixing_damping != "none":
        raise ValueError(
            "mixing_damping is a staleness policy: it needs per-edge ages, "
            "which only the async engine produces — pass async_mode="
            '"sync"/"bounded"/"full" (synchronous gossip has zero ages, so '
            "damping would be a silent no-op)"
        )
    from repro.obs import as_obs, scan_heartbeat

    obs = as_obs(obs)
    state = init_state(problem, cfg, x0, y0)

    def body(st, inputs):
        k, W = inputs
        t_idx = st.t  # pre-update round index (starts at 0)
        st, metrics = c2dfb_round(st, k, problem, topo, cfg, W=W)
        # mid-scan liveness for the SYNC scan too (Obs(heartbeat_every=N)):
        # a host-callback effect — no extra jit traces, math untouched
        # (asserted in tests/test_obs.py)
        scan_heartbeat(obs, "sync", t_idx, metrics)
        return st, metrics

    keys = jax.random.split(key, T)
    if schedule is not None:
        from repro.net.dynamic import validate_schedule_stack

        # the base-edge subset check only binds when a fabric prices the
        # run (non-base edges cannot be priced); pure-math scans accept
        # any valid gossip matrix
        Ws = jnp.asarray(
            validate_schedule_stack(
                schedule.stack(T), T, topo.m,
                base=topo if fabric is not None else None,
            ),
            jnp.float32,
        )
    else:
        Ws = jnp.broadcast_to(
            jnp.asarray(topo.W, jnp.float32), (T,) + topo.W.shape
        )
    from repro.async_gossip.engine import record_trace

    cost = mem0 = fleet_oracles = None
    if obs is not None:
        from repro.obs.compute import (
            c2dfb_oracle_calls,
            memory_peak_bytes,
            round_cost,
        )

        # one ROUND body's trip-count-aware cost (memoized; the scan
        # runs T of these) — keyed like the async engines' cost closures
        with obs.span("cost_analysis", engine="sync"):
            cost = round_cost(
                ("c2dfb/sync", id(problem), id(topo), cfg),
                lambda st, k, W: c2dfb_round(
                    st, k, problem, topo, cfg, W=W
                ),
                state, keys[0], Ws[0],
                expected_oracles=c2dfb_oracle_calls(cfg),
                label="c2dfb/sync",
            )
        fleet_oracles = {
            k: v * topo.m for k, v in c2dfb_oracle_calls(cfg).items()
        }
        mem0 = memory_peak_bytes()

    def scanned(s):
        record_trace("sync_scan")  # one bump per (re)trace of the scan
        return jax.lax.scan(body, s, (keys, Ws))

    if jit:
        # donate the state carry so XLA reuses its buffers for the output
        # state in place; init_state aliases x0/y0, which callers reuse
        # across runs, so the carry gets fresh buffers first
        state = donate_copy(state)
        scan = jax.jit(scanned, donate_argnums=0)
    else:
        scan = scanned
    if obs is not None:
        with obs.span("scan", engine="sync"):
            state, metrics = scan(state)
            jax.block_until_ready(metrics)
    else:
        state, metrics = scan(state)
    metrics = add_measured_bytes(metrics, state.x)
    if fabric is not None:
        phases, labels = round_phases(state, cfg, fabric.topo, key)
        sim_s, wire_b = [], []
        for t in range(T):
            phases_t = phases
            if schedule is not None:
                # only the round's active links carry traffic
                act = set(schedule.active_edges(t))
                phases_t = [
                    {e: b for e, b in ph.items() if e in act} for ph in phases
                ]
            rep = fabric.simulate_round(phases_t, t, labels=labels)
            sim_s.append(rep["sim_seconds"])
            wire_b.append(rep["wire_bytes"])
        metrics = dict(metrics)
        metrics["sim_seconds"] = np.asarray(sim_s)
        metrics["wire_bytes"] = np.asarray(wire_b, dtype=np.int64)
    if obs is not None:
        host = {k: np.asarray(v) for k, v in metrics.items()}
        for t in range(T):
            obs.round(
                "sync", t, {k: v[t] for k, v in host.items()},
                oracle_calls=fleet_oracles,
                compute_flops=cost.flops,
                hbm_bytes=cost.hbm_bytes,
                compile_seconds=cost.compile_seconds if t == 0 else None,
                memory_peak_bytes=mem0 if t == 0 else None,
            )
            # schema-v2 node rows: the sync scan knows per-node consensus
            # distance; byte/staleness signals stay None (the barrier path
            # accounts bytes fleet-wide, and all ages are zero)
            x_nd = host["x_node_dist"][t]
            for i in range(x_nd.shape[0]):
                obs.node(
                    "sync", t, i,
                    {
                        "x_dist": x_nd[i],
                        "compute_flops": cost.flops / topo.m,
                    },
                )
    return state, metrics
