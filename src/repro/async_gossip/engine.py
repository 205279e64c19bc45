"""Async execution engine: C2DFB (and the baselines) under staleness.

Couples the three halves of the subsystem:

* `scheduler.AsyncScheduler` (host-side numpy) turns the fabric's link /
  straggler timelines into per-step, per-edge version AGES;
* `mixing.mix_delta_delayed` (jit) gates the mixing matrix with those ages
  inside ``lax.scan``;
* `ledger.StalenessLedger` keeps the ages and the consensus-vs-seconds
  curve as first-class round metrics.

The outer loop here runs EAGERLY round-by-round (the jitted work is
per-round): each round the current residuals are serialized by the wire
codec to get honest per-node packet sizes, the scheduler executes the two
inner loops event-driven (outer x / s_x broadcasts stay
barrier-synchronized — Algorithm 1's round boundary, which also drains
in-flight residuals so the next round's version-0 references are globally
consistent), and the resulting age tensors ride into the jitted round as
scan inputs.  `repro.async_gossip.compiled` is the two-phase twin: it
replays the same scheduler up front with ANALYTIC payload sizes
(`analytic_message_bytes`) and runs all T rounds as ONE jitted
``lax.scan`` over the stacked age tensors — same math
(`c2dfb_masked_round` is the single round body both paths jit), byte
accuracy traded only in the timing model.

Rounds whose age tensors are all zero take a fast path that is
OP-IDENTICAL to the synchronous `c2dfb_round` — so a zero-latency fabric
reproduces the synchronous trajectory bit-for-bit (tested), not merely to
tolerance.  The fast path is a ``lax.cond`` branch inside the one jitted
round body, so selecting it never retraces.
"""

from __future__ import annotations

import dataclasses
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np

from repro.async_gossip.ledger import StalenessLedger
from repro.async_gossip.mixing import (
    init_history,
    mix_delta_delayed,
    push_history,
)
from repro.async_gossip.scheduler import AsyncScheduler
from repro.core.bilevel_problem import BilevelProblem
from repro.core.c2dfb import (
    C2DFBConfig,
    C2DFBState,
    add_measured_bytes,
    c2dfb_round_core,
    init_state,
)
from repro.core.compression import make_compressor
from repro.core.inner_loop import (
    InnerState,
    inner_apply,
    inner_loop,
    inner_message_bytes,
)
from repro.core.topology import Topology
from repro.core.types import Pytree, consensus_error, tree_sq_norm
from repro.obs.scopes import scope

#: Payload-size models for the eager engine: "measured" serializes the
#: CURRENT residuals every round (codec truth, byte-accurate timing),
#: "analytic" prices every round with the constant
#: `analytic_message_bytes` size — the compiled runtime's timing model,
#: exposed here so eager-vs-compiled trajectory parity can be asserted
#: under identical timelines.
PAYLOAD_MODES = ("measured", "analytic")

# ---------------------------------------------------------------------------
# trace accounting + the one keyed jit cache every engine path shares
# ---------------------------------------------------------------------------

#: Python-trace counters, bumped at TRACE time inside the round bodies —
#: a retrace shows up as an increment, so tests and benchmarks can assert
#: the compiled path compiles once (not O(T)) and the eager path never
#: retraces across rounds.
_TRACE_COUNTS: dict[str, int] = {}


def record_trace(name: str) -> None:
    """Bump a named trace counter (called from inside traced functions, so
    it fires once per compilation, not per execution)."""
    _TRACE_COUNTS[name] = _TRACE_COUNTS.get(name, 0) + 1


def trace_counts() -> dict[str, int]:
    """Snapshot of the per-body trace counters."""
    return dict(_TRACE_COUNTS)


def reset_trace_counts() -> None:
    _TRACE_COUNTS.clear()


@contextmanager
def preserve_trace_counts():
    """Snapshot/restore the trace counters around a bookkeeping trace —
    the compute meter (`repro.obs.compute.round_cost`) lowers a round
    body purely for HLO cost analysis, and that lowering must not show
    up as a retrace in the counters the benchmarks pin."""
    saved = dict(_TRACE_COUNTS)
    try:
        yield
    finally:
        _TRACE_COUNTS.clear()
        _TRACE_COUNTS.update(saved)


def cached_jit(cache: dict, key: tuple, build, **jit_kwargs):
    """The ONE keyed jit-cache helper for every engine path (C2DFB,
    MADSBO, MDBO, eager and compiled): ``build()`` is called once per
    ``key`` and the jitted result memoized in ``cache``.

    Each run owns a private cache by default; callers that pass the same
    dict across runs (``fn_cache=...`` on the run functions — the
    benchmark's warm-timing axis does) share compilations, which is safe
    exactly when the key captures everything the closure bakes in — keys
    therefore carry ``id(problem)`` / ``id(topo)`` plus the config and
    policy knobs."""
    fn = cache.get(key)
    if fn is None:
        fn = cache[key] = jax.jit(build(), **jit_kwargs)
    return fn


#: analytic packet sizes depend only on (compressor spec, leaf shapes) —
#: memoized so repeated runs skip the probe's compress + serialize pass
_ANALYTIC_BYTES_CACHE: dict = {}


def analytic_message_bytes(inner: InnerState, compressor) -> int:
    """Per-node steady-state wire bytes of one inner step's two messages
    (d- and s-residual), from the compression SPEC alone: a dense
    all-ones probe residual is compressed and serialized by the wire codec
    (`repro.net.wire.measure_tree_bytes`).  Every shipped format is
    size-deterministic on a dense probe — sparse top-k keeps exactly its
    budget per leaf/block, quant and dense payloads are shape-static — so
    this is the exact steady-state packet size without touching run-time
    values.  The compiled runtime prices every round with this constant;
    that is the one place its timing model departs from the eager
    engine's per-round codec-measured sizes (byte accuracy traded, math
    unchanged)."""
    from repro.net.wire import measure_tree_bytes

    leaves = jax.tree.leaves(inner.d_hat)
    try:
        ckey = (
            compressor,
            tuple((l.shape[1:], str(l.dtype)) for l in leaves),
        )
        cached = _ANALYTIC_BYTES_CACHE.get(ckey)
        if cached is not None:
            return cached
    except TypeError:  # unhashable custom compressor: just measure
        ckey = None
    probe = jax.tree.map(lambda v: jnp.ones_like(v[0]), inner.d_hat)
    q = compressor.compress_tree(jax.random.PRNGKey(0), probe)
    nbytes = 2 * measure_tree_bytes(compressor, q)
    if ckey is not None:
        _ANALYTIC_BYTES_CACHE[ckey] = nbytes
    return nbytes


def async_inner_loop(
    state: InnerState,
    key: jax.Array,
    grad_fn,
    W: jax.Array,
    compressor,
    gamma: float,
    eta: float,
    K: int,
    ages: jax.Array,
    depth: int,
    delayed: bool = True,
    damping: str = "none",
    decay: float = 0.5,
    hist0: tuple | None = None,
    return_hist: bool = False,
) -> tuple:
    """Algorithm 2 under staleness: K steps where the mixing deltas come
    from age-gated reference HISTORIES instead of the current references.

    ``ages`` is (K, m, m) — step k mixes edge (i, j) on the common version
    of age ``ages[k, i, j]``.  With ``delayed=False`` (all ages zero) this
    IS the synchronous `inner_loop` — same function, so zero-staleness
    rounds are bit-identical to the sync path and carry no dead history.

    ``damping`` applies the staleness-adaptive weight policy
    (`mixing.DAMPING_POLICIES`) per step on the realized ages.  ``hist0``
    (a ``(hist_d, hist_s)`` pair) seeds the reference histories instead of
    re-initializing them from the current references — the schedule-
    composed engine carries histories ACROSS rounds so edges that sat
    rounds out can still mix their true, frozen version (their re-entry
    age points past the current round's pushes).  With ``return_hist`` the
    post-loop histories ride back to the caller as a third result.

    The delayed branch mirrors `inner_loop`'s scan body with the history
    carry added; keep the two in lockstep (same `inner_apply` call, same
    byte metering, same metrics keys) — a change to one that skips the
    other breaks the sync/async metric parity that `run` callers rely on.
    """
    from repro.net.wire import scan_tree_bytes

    if not delayed:
        if return_hist:
            raise ValueError("return_hist requires the delayed branch")
        return inner_loop(
            state, key, grad_fn, W, compressor, gamma, eta, K
        )

    if hist0 is None:
        hist_d = init_history(state.d_hat, depth)
        hist_s = init_history(state.s_hat, depth)
    else:
        hist_d, hist_s = hist0

    def body(carry, inp):
        st, hd, hs = carry
        k, age_k = inp
        mix_d = mix_delta_delayed(W, hd, age_k, damping, decay)
        mix_s = mix_delta_delayed(W, hs, age_k, damping, decay)
        st, (q_d, q_s) = inner_apply(
            st, k, grad_fn, compressor, gamma, eta, mix_d, mix_s
        )
        hd = push_history(hd, st.d_hat)
        hs = push_history(hs, st.s_hat)
        nbytes = scan_tree_bytes(compressor, q_d) + scan_tree_bytes(
            compressor, q_s
        )
        return (st, hd, hs), nbytes

    keys = jax.random.split(key, K)
    ages = jnp.asarray(ages, jnp.int32)
    (state, hist_d, hist_s), step_bytes = jax.lax.scan(
        body, (state, hist_d, hist_s), (keys, ages)
    )
    with scope("meter"):
        metrics = {
            "consensus_err": consensus_error(state.d),
            "compress_err": tree_sq_norm(
                jax.tree.map(jnp.subtract, state.d, state.d_hat)
            ),
            "msg_bytes": jnp.sum(step_bytes),
        }
    if return_hist:
        return state, metrics, (hist_d, hist_s)
    return state, metrics


def async_c2dfb_round(
    state: C2DFBState,
    key: jax.Array,
    problem: BilevelProblem,
    topo: Topology,
    cfg: C2DFBConfig,
    ages_y: jax.Array,
    ages_z: jax.Array,
    depth: int,
    delayed: bool = True,
    W: jax.Array | None = None,
    damping: str = "none",
    decay: float = 0.5,
    hists: dict | None = None,
) -> tuple:
    """One outer round with staleness-gated inner loops: the shared
    `c2dfb_round_core` body with `async_inner_loop` plugged in.  Outer
    x / s_x updates stay synchronous (the round boundary is a barrier), so
    zero ages reproduce the synchronous round exactly.

    ``W`` overrides the static mixing matrix with a schedule round's
    matrix (outer AND inner mixing — inactive edges carry zero weight,
    so their ages never contribute).  ``hists`` maps loop tag ("y" / "z")
    to a cross-round ``(hist_d, hist_s)`` history pair; when given, the
    round returns ``(state, metrics, hists_out)`` with the post-loop
    histories so the engine can thread them into the next round."""
    Wm = jnp.asarray(topo.W if W is None else W, dtype=jnp.float32)
    compressor = cfg.make_compressor()
    ages = {"y": ages_y, "z": ages_z}
    hists_out: dict = {}

    def inner_fn(st, k, grad_fn, eta, tag):
        if hists is None:
            return async_inner_loop(
                st, k, grad_fn, Wm, compressor, cfg.gamma_in, eta, cfg.K,
                ages[tag], depth, delayed, damping=damping, decay=decay,
            )
        st, mets, h = async_inner_loop(
            st, k, grad_fn, Wm, compressor, cfg.gamma_in, eta, cfg.K,
            ages[tag], depth, delayed, damping=damping, decay=decay,
            hist0=hists[tag], return_hist=True,
        )
        hists_out[tag] = h
        return st, mets

    new_state, metrics = c2dfb_round_core(
        state, key, problem, Wm, cfg, inner_fn
    )
    if hists is None:
        return new_state, metrics
    return new_state, metrics, hists_out


def _dense_node_bytes(tree: Pytree) -> int:
    """Per-node dense f32 wire bytes of a node-stacked tree (codec truth)."""
    from repro.net.wire import codec_for

    one = jax.tree.map(lambda v: v[0], tree)
    return codec_for(make_compressor("identity")).tree_bytes(one)


@dataclasses.dataclass(frozen=True)
class _RunPlan:
    """Everything a C2DFB async run fixes BEFORE its first round — shared
    by the eager loop and the compiled replay so the two paths cannot
    drift: the (static) history depth, the validated schedule stack and
    its per-round active-edge masks, the re-entry catch-up packet size,
    the cross-round history seed, and whether version lag must be
    tracked."""

    depth: int
    Ws: object = None           # (T, m, m) validated schedule stack
    masks: object = None        # (T, m, m) bool active-edge masks
    catchup_bytes: int = 0
    hists: dict | None = None
    track_lag: bool = False


def _prepare_async_run(
    scheduler: AsyncScheduler, state, cfg, topo, T: int, schedule
) -> _RunPlan:
    """Size the histories and resolve the schedule/lag bookkeeping for a
    run (see `_RunPlan`).  An injected scheduler may carry unresolved
    version lag from a prior schedule-composed run (edges still dropped at
    that run's end); a static follow-up run must honor it — those edges
    re-enter at their true age with a priced catch-up, not silently at
    age 0."""
    depth = scheduler.depth_for(cfg.K)
    catchup_bytes = 0
    hists = None
    Ws = masks = None
    carried_lag = int(scheduler.version_lag.max())
    if schedule is None and carried_lag > 0:
        catchup_bytes = 2 * _dense_node_bytes(state.inner_y.d_hat)
        depth = scheduler.depth_for(cfg.K, carried_lag)
    if schedule is not None:
        from repro.net.dynamic import (
            active_edge_masks,
            schedule_version_lags,
            validate_schedule_stack,
        )

        Ws = validate_schedule_stack(schedule.stack(T), T, topo.m, base=topo)
        masks = active_edge_masks(Ws)
        _, max_lag = schedule_version_lags(masks, cfg.K)
        # every realizable age is bounded by the replayed lag plus the
        # carried offset (conservative: a carried edge's re-entry lag is
        # its replayed lag + at most its entry lag)
        depth = scheduler.depth_for(cfg.K, int(max_lag) + carried_lag)
        # re-entering edges exchange both dense reference trees first
        catchup_bytes = 2 * _dense_node_bytes(state.inner_y.d_hat)
        hists = {
            "y": (
                init_history(state.inner_y.d_hat, depth),
                init_history(state.inner_y.s_hat, depth),
            ),
            "z": (
                init_history(state.inner_z.d_hat, depth),
                init_history(state.inner_z.s_hat, depth),
            ),
        }
    return _RunPlan(
        depth=depth, Ws=Ws, masks=masks, catchup_bytes=catchup_bytes,
        hists=hists, track_lag=schedule is not None or carried_lag > 0,
    )


# ---------------------------------------------------------------------------
# the single age-masked round bodies (jitted once per run, shared by the
# eager engine and the compiled lax.scan runtime)
# ---------------------------------------------------------------------------


def c2dfb_masked_round(
    state: C2DFBState,
    key: jax.Array,
    ages_y: jax.Array,
    ages_z: jax.Array,
    *,
    problem: BilevelProblem,
    topo: Topology,
    cfg: C2DFBConfig,
    depth: int,
    damping: str = "none",
    decay: float = 0.5,
) -> tuple[C2DFBState, dict]:
    """ONE C2DFB round body for every age pattern: a ``lax.cond`` on
    "any nonzero age" selects between the delayed round and the
    synchronous fast path, so zero-staleness rounds stay bit-identical to
    the sync algorithm (same ops as `inner_loop`) while the whole thing
    jits exactly once per run — no per-``delayed``-value retrace, and the
    same body can ride a `lax.scan` with the ages as traced inputs
    (`repro.async_gossip.compiled`)."""
    record_trace("c2dfb_round")

    def _delayed(st, k, ay, az):
        return async_c2dfb_round(
            st, k, problem, topo, cfg, ay, az, depth, delayed=True,
            damping=damping, decay=decay,
        )

    def _sync(st, k, ay, az):
        return async_c2dfb_round(
            st, k, problem, topo, cfg, ay, az, depth, delayed=False,
        )

    stale = jnp.logical_or(jnp.any(ages_y != 0), jnp.any(ages_z != 0))
    return jax.lax.cond(stale, _delayed, _sync, state, key, ages_y, ages_z)


def c2dfb_schedule_round(
    state: C2DFBState,
    key: jax.Array,
    W: jax.Array,
    ages_y: jax.Array,
    ages_z: jax.Array,
    hists: dict,
    *,
    problem: BilevelProblem,
    topo: Topology,
    cfg: C2DFBConfig,
    depth: int,
    damping: str = "none",
    decay: float = 0.5,
) -> tuple:
    """The schedule-composed round body: W, ages and the cross-round
    histories all ride as traced arguments, so every schedule round (and
    the compiled scan over all of them) shares one compilation."""
    record_trace("c2dfb_round")
    return async_c2dfb_round(
        state, key, problem, topo, cfg, ages_y, ages_z, depth, delayed=True,
        W=W, damping=damping, decay=decay, hists=hists,
    )


def async_round_cost(
    problem: BilevelProblem,
    topo: Topology,
    cfg: C2DFBConfig,
    plan: _RunPlan,
    mixing_damping: str,
    damping_decay: float,
    state: C2DFBState,
    key: jax.Array,
):
    """Trip-count-aware `repro.obs.compute.RoundCost` of the ONE masked
    round body this run jits — memoized on the same ``id(problem)`` /
    config key discipline as `cached_jit`, WITHOUT the donate/heartbeat
    key components (they change buffers and effects, not FLOPs), so the
    eager engine, the compiled scan and SimTransport delegation all
    resolve to one analysis and report identical ``compute_flops``.
    The fresh lowering's oracle sites are checked against the
    closed-form `c2dfb_oracle_calls` structure (zero hvp/jvp sites)."""
    from repro.obs.compute import c2dfb_oracle_calls, round_cost

    expected = c2dfb_oracle_calls(cfg)
    m = topo.m
    ages0 = jnp.zeros((cfg.K, m, m), jnp.int32)
    base = (
        id(problem), id(topo), cfg, plan.depth, mixing_damping,
        damping_decay,
    )
    if plan.Ws is None:
        return round_cost(
            ("c2dfb/cost",) + base,
            lambda st, k, ay, az: c2dfb_masked_round(
                st, k, ay, az, problem=problem, topo=topo, cfg=cfg,
                depth=plan.depth, damping=mixing_damping,
                decay=damping_decay,
            ),
            state, key, ages0, ages0,
            expected_oracles=expected, label="c2dfb",
        )
    return round_cost(
        ("c2dfb/cost-schedule",) + base,
        lambda st, k, Wt, ay, az, hs: c2dfb_schedule_round(
            st, k, Wt, ay, az, hs, problem=problem, topo=topo, cfg=cfg,
            depth=plan.depth, damping=mixing_damping, decay=damping_decay,
        ),
        state, key, jnp.asarray(plan.Ws[0], jnp.float32), ages0, ages0,
        plan.hists,
        expected_oracles=expected, label="c2dfb",
    )


def baseline_round_cost(
    alg: str, problem, topo, cfg, depth: int, damping: str, decay: float,
    state,
):
    """`async_round_cost`'s MADSBO/MDBO twin: the cost of the one
    `baseline_masked_round` body both the eager loop and the compiled
    scan jit, memoized under the `_baseline_round_fn` key discipline and
    structure-checked against the second-order closed forms (nonzero
    hvp/jvp sites — the counterpoint to C2DFB's zeros)."""
    from repro.obs.compute import oracle_calls_for, round_cost

    expected = oracle_calls_for(alg, cfg)
    m = topo.m
    ages_ll = jnp.zeros((cfg.K, m, m), jnp.int32)
    ckey = (
        "baseline/cost", alg, id(problem), id(topo), cfg, depth, damping,
        decay,
    )
    if alg == "madsbo":
        ages_h = jnp.zeros((cfg.Q, m, m), jnp.int32)
        return round_cost(
            ckey,
            lambda st, al, ah: baseline_masked_round(
                alg, st, al, ah, problem=problem, topo=topo, cfg=cfg,
                depth=depth, damping=damping, decay=decay,
            ),
            state, ages_ll, ages_h,
            expected_oracles=expected, label=alg,
        )
    return round_cost(
        ckey,
        lambda st, al: baseline_masked_round(
            alg, st, al, problem=problem, topo=topo, cfg=cfg,
            depth=depth, damping=damping, decay=decay,
        ),
        state, ages_ll,
        expected_oracles=expected, label=alg,
    )


def run_async(
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
    payload_bytes: str = "measured",
    fn_cache: dict | None = None,
    obs=None,
) -> tuple[C2DFBState, dict]:
    """T outer rounds of C2DFB under the async engine (eager outer loop —
    the byte-accurate reference; `repro.async_gossip.compiled` is the
    single-scan twin).

    ``obs`` (a `repro.obs.Obs` or bare `MetricsSink`) streams one
    structured record per round — the shared `repro.obs.records` schema,
    with bytes split by stream, staleness stats, simulated and host wall
    seconds, and the jit trace-counter snapshot — as the round completes
    (a killed run keeps every finished round's record).

    Returns the final state and per-round metric arrays — the synchronous
    ``run``'s keys plus ``sim_seconds``, ``wire_bytes`` (per-link
    accounting from the scheduler), ``staleness_max`` / ``staleness_mean``
    (active directed edges only) and ``staleness_hist`` (T, depth) age
    histograms.  ``policy="sync"`` is the barrier reference; "bounded"
    enforces ``age <= bound`` by gating; "full" never waits.

    ``payload_bytes`` selects the timing model's packet sizes
    (`PAYLOAD_MODES`): "measured" serializes the current residuals every
    round, "analytic" prices every round with the compiled runtime's
    constant `analytic_message_bytes` size — feed both engines "analytic"
    and their trajectories must agree array-for-array
    (tests/test_compiled_async.py).  ``fn_cache`` shares the round-body
    jit cache across runs (see `cached_jit`).

    ``version_rule`` selects which version an edge mixes (the scheduler's
    `VERSION_RULES`): ``"common"`` — the idealized newest commonly-held
    version (default, bit-exact with pre-rule trajectories);
    ``"deterministic"`` — exactly version k - S, realizable with no
    coordination at the same wait times; ``"acked"`` — common freshness
    with the agreement priced as sequence-number acks on the wire (an
    ``ack`` stream in the byte accounting).  Ignored when an explicit
    ``scheduler`` is injected (its own rule wins).

    ``schedule`` (a `repro.net.dynamic.TopologySchedule`) composes the
    async engine with per-round mixing matrices: each round runs on the
    schedule's active edge set; an edge that sits rounds out freezes its
    reference history and re-enters with its true version age (the
    scheduler's persistent ``version_lag``), paying a dense catch-up
    transfer before in-round residuals apply.  Reference histories are
    carried ACROSS rounds so the frozen versions stay addressable.
    ``mixing_damping`` selects the staleness-adaptive weight policy
    (`mixing.DAMPING_POLICIES`) — ``"inverse-age"`` keeps the fully-async
    policy contractive at mixing steps where undamped delayed gossip
    diverges (tests/test_async_schedule_compose.py).
    """
    from repro.async_gossip.ledger import (
        edge_age_samples,
        node_staleness_stats,
        staleness_stats,
    )
    from repro.async_gossip.mixing import validate_damping
    from repro.net.fabric import edge_list
    from repro.obs import as_obs
    from repro.transport.base import as_transport

    obs = as_obs(obs)
    validate_damping(mixing_damping)
    if payload_bytes not in PAYLOAD_MODES:
        raise ValueError(
            f"unknown payload_bytes {payload_bytes!r}; have {PAYLOAD_MODES}"
        )
    # accept a Transport wherever a fabric is accepted; the scheduler
    # consumes arrival times through the transport face either way
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
    depth = plan.depth
    hists = plan.hists
    const_bytes = (
        analytic_message_bytes(state.inner_y, comp)
        if payload_bytes == "analytic" else None
    )

    cache = fn_cache if fn_cache is not None else {}
    ckey = (
        id(problem), id(topo), cfg, depth, mixing_damping, damping_decay,
    )
    if schedule is not None:
        sched_round = cached_jit(
            cache, ("c2dfb/schedule",) + ckey,
            lambda: lambda st, k, Wt, ay, az, hs: c2dfb_schedule_round(
                st, k, Wt, ay, az, hs, problem=problem, topo=topo, cfg=cfg,
                depth=depth, damping=mixing_damping, decay=damping_decay,
            ),
        )
    else:
        round_fn = cached_jit(
            cache, ("c2dfb/masked",) + ckey,
            lambda: lambda st, k, ay, az: c2dfb_masked_round(
                st, k, ay, az, problem=problem, topo=topo, cfg=cfg,
                depth=depth, damping=mixing_damping, decay=damping_decay,
            ),
        )

    keys = jax.random.split(key, T)
    cost = mem0 = fleet_oracles = None
    if obs is not None:
        from repro.obs.compute import c2dfb_oracle_calls, memory_peak_bytes

        with obs.span("cost_analysis", engine="async-eager"):
            cost = async_round_cost(
                problem, topo, cfg, plan, mixing_damping, damping_decay,
                state, keys[0],
            )
        fleet_oracles = {
            k: v * topo.m for k, v in c2dfb_oracle_calls(cfg).items()
        }
        mem0 = memory_peak_bytes()
    rows: list[dict] = []
    for t in range(T):
        w0 = obs.hostspans.now() if obs is not None else 0.0
        active_t = plan.masks[t] if plan.masks is not None else None
        if active_t is not None:
            act_edges = tuple(
                (i, j) for i, j in edges if active_t[i, j]
            )
        else:
            act_edges = edges
        if const_bytes is not None:
            bytes_y = bytes_z = const_bytes
        else:
            # honest per-node packet sizes: serialize CURRENT residuals
            kb = jax.random.fold_in(keys[t], 0xB17E)  # metering-only key
            kby, kbz = jax.random.split(kb)
            bd, bs = inner_message_bytes(state.inner_y, comp, kby)
            bytes_y = np.asarray(bd) + np.asarray(bs)
            bd, bs = inner_message_bytes(state.inner_z, comp, kbz)
            bytes_z = np.asarray(bd) + np.asarray(bs)

        rt = scheduler.drive_round(
            t, cfg.K, bytes_y, bytes_z, outer_node_bytes, compute_step,
            active=active_t, catchup_bytes=plan.catchup_bytes,
            track_lag=plan.track_lag,
        )
        tl_y, tl_z = rt.tl_y, rt.tl_z

        if schedule is not None:
            state, mets, hists = sched_round(
                state, keys[t], jnp.asarray(plan.Ws[t], jnp.float32),
                jnp.asarray(tl_y.ages), jnp.asarray(tl_z.ages), hists,
            )
        else:
            state, mets = round_fn(
                state, keys[t], jnp.asarray(tl_y.ages),
                jnp.asarray(tl_z.ages),
            )

        ledger.record_loop(t, "y", tl_y.ages, tl_y.start_s(rt.x_end),
                           tl_y.end_s, edges=act_edges)
        ledger.record_loop(t, "z", tl_z.ages, tl_z.start_s(tl_y.end_s),
                           tl_z.end_s, edges=act_edges)
        x_err = float(mets["x_consensus_err"])
        ledger.record_point(rt.t_end, x_err)

        edge_ages = edge_age_samples((tl_y.ages, tl_z.ages), act_edges)
        row = add_measured_bytes(
            {k: np.asarray(v) for k, v in mets.items()}, state.x
        )
        row["sim_seconds"] = np.float64(rt.t_end - rt.t_start)
        row["wire_bytes"] = np.int64(
            tl_y.wire_bytes + tl_z.wire_bytes + rt.outer_wire_bytes
        )
        smax, smean, shist = staleness_stats(edge_ages, depth)
        row["staleness_max"] = smax
        row["staleness_mean"] = smean
        row["staleness_hist"] = shist
        rows.append(row)
        if obs is not None:
            w1 = obs.hostspans.now()
            obs.hostspans.add(f"round[{t}]", w0, w1)
            obs.round(
                "async-eager", t, row,
                bytes_by_stream=rt.wire_bytes_by_stream,
                wall_seconds=w1 - w0, trace_counts=trace_counts(),
                oracle_calls=fleet_oracles,
                compute_flops=cost.flops,
                hbm_bytes=cost.hbm_bytes,
                compile_seconds=cost.compile_seconds if t == 0 else None,
                memory_peak_bytes=mem0 if t == 0 else None,
            )
            # schema-v2 node rows: per-sender egress from the scheduler's
            # accounting, per-node consensus distance from the round body,
            # per-node staleness over each node's incident in-edges
            node_wire = rt.node_wire_bytes
            nmax, nmean = node_staleness_stats(
                (tl_y.ages, tl_z.ages), act_edges, topo.m
            )
            x_nd = np.asarray(mets["x_node_dist"])
            for i in range(topo.m):
                obs.node(
                    "async-eager", t, i,
                    {
                        "x_dist": x_nd[i],
                        "wire_bytes": node_wire[i],
                        "staleness_max": nmax[i],
                        "staleness_mean": nmean[i],
                        "compute_flops": cost.flops / topo.m,
                    },
                    bytes_by_stream=rt.node_bytes_by_stream(i),
                )

    metrics = {
        k: np.stack([r[k] for r in rows]) for k in rows[0]
    } if rows else {}
    metrics["ledger"] = ledger
    return state, metrics


# ---------------------------------------------------------------------------
# baselines under the same scheduler (delayed VALUE gossip: no reference
# points — each step transmits the dense iterate, staleness delays it)
# ---------------------------------------------------------------------------


def delayed_value_scan(
    value: Pytree,
    W: jax.Array,
    gamma: float,
    ages: jax.Array,
    depth: int,
    local_update,
    damping: str = "none",
    decay: float = 0.5,
) -> Pytree:
    """Staleness-gated twin of `repro.core.baselines.value_gossip_scan`:
    K steps of  v <- local_update(v + gamma * mix(views), v_pre)  where the
    views are age-gated versions of the transmitted iterate (dense value
    gossip — each step transmits the iterate itself).  ``local_update``
    has the same (mixed, pre) contract as the synchronous scan.
    ``damping`` applies the same staleness-adaptive weight policy as the
    C2DFB engine (`mixing.DAMPING_POLICIES`)."""
    hist = init_history(value, depth)

    def body(carry, age_k):
        v, h = carry
        delta = mix_delta_delayed(W, h, age_k, damping, decay)
        mixed = jax.tree.map(lambda a, d_: a + gamma * d_, v, delta)
        v_new = local_update(mixed, v)
        h = push_history(h, v_new)
        return (v_new, h), None

    (value, _), _ = jax.lax.scan(
        body, (value, hist), jnp.asarray(ages, jnp.int32)
    )
    return value


def baseline_masked_round(
    alg: str,
    state,
    ages_ll: jax.Array,
    ages_h: jax.Array | None = None,
    *,
    problem: BilevelProblem,
    topo: Topology,
    cfg,
    depth: int,
    damping: str = "none",
    decay: float = 0.5,
) -> tuple:
    """The baselines' single age-masked round body (MADSBO / MDBO twin of
    `c2dfb_masked_round`): one jit per run, ``lax.cond`` keeps zero-age
    rounds bit-identical to the synchronous value-gossip scans, and the
    same body rides the compiled ``lax.scan``."""
    from repro.core.baselines import madsbo_round_async, mdbo_round_async

    record_trace(f"{alg}_round")
    if alg == "madsbo":
        def _delayed(st, al, ah):
            return madsbo_round_async(
                st, problem, topo, cfg, al, ah, depth, delayed=True,
                damping=damping, decay=decay,
            )

        def _sync(st, al, ah):
            return madsbo_round_async(
                st, problem, topo, cfg, al, ah, depth, delayed=False,
            )

        stale = jnp.logical_or(jnp.any(ages_ll != 0), jnp.any(ages_h != 0))
        return jax.lax.cond(stale, _delayed, _sync, state, ages_ll, ages_h)

    def _delayed_m(st, al):
        return mdbo_round_async(
            st, problem, topo, cfg, al, depth, delayed=True,
            damping=damping, decay=decay,
        )

    def _sync_m(st, al):
        return mdbo_round_async(
            st, problem, topo, cfg, al, depth, delayed=False,
        )

    return jax.lax.cond(
        jnp.any(ages_ll != 0), _delayed_m, _sync_m, state, ages_ll
    )


@dataclasses.dataclass(frozen=True)
class BaselineRoundTimeline:
    """One baseline round's scheduler execution (drive/replay unit —
    ``tl_h`` is None for MDBO, whose Neumann terms are local compute).
    ``outer_wire_bytes`` is the upper-level barrier's dense traffic (the
    per-stream split the `repro.obs` round record carries);
    ``outer_node_wire_bytes`` its per-sender split.  Under
    ``version_rule="acked"`` the loops' ack traffic is reported as a
    separate ``ack`` stream (key present only when nonzero — same
    convention as `RoundTimeline`)."""

    tl_ll: object
    tl_h: object | None
    t_start: float
    t_end: float
    outer_wire_bytes: int = 0
    outer_node_wire_bytes: np.ndarray | None = None

    @property
    def wire_bytes_by_stream(self) -> dict[str, int]:
        ack = int(self.tl_ll.ack_wire_bytes)
        by = {
            "outer": int(self.outer_wire_bytes),
            "ll": int(self.tl_ll.wire_bytes) - int(self.tl_ll.ack_wire_bytes),
        }
        if self.tl_h is not None:
            ack += int(self.tl_h.ack_wire_bytes)
            by["higp"] = (
                int(self.tl_h.wire_bytes) - int(self.tl_h.ack_wire_bytes)
            )
        if ack:
            by["ack"] = ack
        return by

    @property
    def wire_bytes(self) -> int:
        return sum(self.wire_bytes_by_stream.values())

    @property
    def node_wire_bytes(self) -> np.ndarray | None:
        """(m,) per-sender egress over the whole round (upper-level
        barrier + value-gossip loops, acks included); sums to
        ``wire_bytes`` exactly — the schema-v2 node-row accounting."""
        parts = [self.outer_node_wire_bytes, self.tl_ll.node_wire_bytes]
        if self.tl_h is not None:
            parts.append(self.tl_h.node_wire_bytes)
        if any(p is None for p in parts):
            return None
        return np.sum(parts, axis=0)

    def node_bytes_by_stream(self, i: int) -> dict[str, int] | None:
        """Node ``i``'s egress split by stream — per-node companion to
        `wire_bytes_by_stream`."""
        if self.node_wire_bytes is None:
            return None

        def _ack(tl) -> int:
            a = tl.node_ack_wire_bytes
            return int(a[i]) if a is not None else 0

        ack = _ack(self.tl_ll)
        by = {
            "outer": int(self.outer_node_wire_bytes[i]),
            "ll": int(self.tl_ll.node_wire_bytes[i]) - _ack(self.tl_ll),
        }
        if self.tl_h is not None:
            ack += _ack(self.tl_h)
            by["higp"] = (
                int(self.tl_h.node_wire_bytes[i]) - _ack(self.tl_h)
            )
        if ack:
            by["ack"] = ack
        return by


def drive_baseline_round(
    scheduler: AsyncScheduler,
    alg: str,
    round_idx: int,
    K: int,
    Q: int,
    N: int,
    dy_bytes: int,
    dx_bytes: int,
    compute_step: float,
) -> BaselineRoundTimeline:
    """One MADSBO/MDBO round's scheduler timeline: the LL value-gossip
    loop (plus MADSBO's HIGP loop), the drain, and the upper-level
    barrier.  Shared by the eager loop and the compiled replay — MDBO's
    Neumann terms are local compute (no gossip in this realization) and
    ride the barrier phase's compute slice."""
    t_start = float(scheduler.clock.max())
    tl_ll = scheduler.run_loop(
        K, dy_bytes, round_idx, compute_step, loop="ll"
    )
    tl_h = None
    if alg == "madsbo":
        tl_h = scheduler.run_loop(
            Q, dy_bytes, round_idx, compute_step, loop="higp"
        )
    scheduler.drain(tl_h.end_s if tl_h is not None else tl_ll.end_s)
    t_end = scheduler.barrier_phase(
        dx_bytes, round_idx, compute_s=compute_step * (1 + N), label="ul"
    )
    outer_node_wire = np.asarray(
        [
            int(dx_bytes) * len(v)
            for v in scheduler.fabric.topo.neighbors
        ],
        dtype=np.int64,
    )
    return BaselineRoundTimeline(
        tl_ll=tl_ll, tl_h=tl_h, t_start=t_start, t_end=t_end,
        outer_wire_bytes=int(outer_node_wire.sum()),
        outer_node_wire_bytes=outer_node_wire,
    )


def run_baseline_async(
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
    compiled: bool = False,
    fn_cache: dict | None = None,
    obs=None,
) -> tuple[object, dict]:
    """MADSBO / MDBO rounds driven by the AsyncScheduler: their dense
    value-gossip loops run event-driven with age-gated mixing; the
    hypergradient assembly and upper-level update stay at the (barrier)
    round boundary, mirroring the sync baselines.  ``mixing_damping``
    applies the staleness-adaptive weight policy to the value-gossip
    loops, same contract as `run_async`.  Baseline payload sizes are
    dense (analytic already), so ``compiled=True`` — precompute the
    timelines and ride one ``lax.scan``
    (`repro.async_gossip.compiled.run_baseline_async_compiled`) — is
    trajectory- AND byte-exact with the eager loop.  ``version_rule``
    selects the edge-version protocol exactly as in `run_async` (the
    scheduler's `VERSION_RULES`; acked runs carry an ``ack`` stream in
    the byte accounting)."""
    from repro.async_gossip.ledger import node_staleness_stats
    from repro.async_gossip.mixing import validate_damping
    from repro.core.baselines import madsbo_init, mdbo_init
    from repro.net.fabric import edge_list
    from repro.obs import as_obs

    if alg not in ("madsbo", "mdbo"):
        raise ValueError(f"unknown async baseline {alg!r}")
    validate_damping(mixing_damping)
    if compiled:
        from repro.async_gossip.compiled import run_baseline_async_compiled

        return run_baseline_async_compiled(
            alg, problem, topo, cfg, x0, y0, T, fabric, policy=policy,
            bound=bound, version_rule=version_rule, ledger=ledger,
            mixing_damping=mixing_damping, damping_decay=damping_decay,
            fn_cache=fn_cache, obs=obs,
        )
    obs = as_obs(obs)
    from repro.transport.base import as_transport

    transport = as_transport(fabric).bind(topo)
    fabric = transport.fabric
    scheduler = AsyncScheduler(
        transport, policy=policy, bound=bound, version_rule=version_rule
    )
    ledger = ledger if ledger is not None else StalenessLedger()
    dy_bytes = _dense_node_bytes(y0)
    dx_bytes = _dense_node_bytes(x0)
    K = cfg.K
    Q = getattr(cfg, "Q", 0)            # MADSBO's HIGP subsolver steps
    N = getattr(cfg, "neumann_N", 0)    # MDBO's local Neumann terms
    n_units = K + Q + N + 1
    compute_step = fabric.compute_s / n_units if fabric.compute_s else 0.0
    depth = scheduler.depth_for(max(K, Q))

    if alg == "madsbo":
        state = madsbo_init(problem, x0, y0)
    else:
        state = mdbo_init(x0, y0)
    cache = fn_cache if fn_cache is not None else {}
    round_fn = _baseline_round_fn(
        cache, alg, problem, topo, cfg, depth, mixing_damping, damping_decay
    )
    edges = edge_list(topo)

    cost = mem0 = fleet_oracles = None
    if obs is not None:
        from repro.obs.compute import memory_peak_bytes, oracle_calls_for

        with obs.span("cost_analysis", engine="baseline-eager"):
            cost = baseline_round_cost(
                alg, problem, topo, cfg, depth, mixing_damping,
                damping_decay, state,
            )
        fleet_oracles = oracle_calls_for(alg, cfg, m=topo.m)
        mem0 = memory_peak_bytes()
    rows = []
    for t in range(T):
        w0 = obs.hostspans.now() if obs is not None else 0.0
        rt = drive_baseline_round(
            scheduler, alg, t, K, Q, N, dy_bytes, dx_bytes, compute_step
        )
        tl_ll, tl_h = rt.tl_ll, rt.tl_h
        if alg == "madsbo":
            state, mets = round_fn(
                state, jnp.asarray(tl_ll.ages), jnp.asarray(tl_h.ages)
            )
        else:
            state, mets = round_fn(state, jnp.asarray(tl_ll.ages))
        ledger.record_loop(t, "ll", tl_ll.ages,
                           tl_ll.start_s(rt.t_start), tl_ll.end_s)
        if tl_h is not None:
            ledger.record_loop(t, "higp", tl_h.ages,
                               tl_h.start_s(tl_ll.end_s), tl_h.end_s)
        x_err = float(mets["x_consensus_err"])
        ledger.record_point(rt.t_end, x_err)
        row = {k: np.asarray(v) for k, v in mets.items()}
        row["sim_seconds"] = np.float64(rt.t_end - rt.t_start)
        row["wire_bytes"] = np.int64(rt.wire_bytes)
        rows.append(row)
        if obs is not None:
            w1 = obs.hostspans.now()
            obs.hostspans.add(f"round[{t}]", w0, w1)
            obs.round(
                "baseline-eager", t, row,
                bytes_by_stream=rt.wire_bytes_by_stream,
                wall_seconds=w1 - w0, trace_counts=trace_counts(),
                oracle_calls=fleet_oracles,
                compute_flops=cost.flops,
                hbm_bytes=cost.hbm_bytes,
                compile_seconds=cost.compile_seconds if t == 0 else None,
                memory_peak_bytes=mem0 if t == 0 else None,
            )
            # schema-v2 node rows, same contract as every other engine:
            # per-sender egress from the scheduler, per-node consensus
            # distance from the round body, per-node staleness over each
            # node's incident in-edges
            node_wire = rt.node_wire_bytes
            ages_list = (
                (tl_ll.ages,) if tl_h is None
                else (tl_ll.ages, tl_h.ages)
            )
            nmax, nmean = node_staleness_stats(ages_list, edges, topo.m)
            x_nd = np.asarray(mets["x_node_dist"])
            for i in range(topo.m):
                obs.node(
                    "baseline-eager", t, i,
                    {
                        "x_dist": x_nd[i],
                        "wire_bytes": node_wire[i],
                        "staleness_max": nmax[i],
                        "staleness_mean": nmean[i],
                        "compute_flops": cost.flops / topo.m,
                    },
                    bytes_by_stream=rt.node_bytes_by_stream(i),
                )

    metrics = {k: np.stack([r[k] for r in rows]) for k in rows[0]}
    metrics["ledger"] = ledger
    return state, metrics


def _baseline_round_fn(
    cache: dict, alg: str, problem, topo, cfg, depth: int,
    damping: str, decay: float,
):
    """The baselines' jitted masked round from the shared keyed cache
    (same helper the C2DFB paths use, so MADSBO/MDBO and C2DFB share one
    compilation store within a run)."""
    ckey = ("baseline", alg, id(problem), id(topo), cfg, depth, damping,
            decay)
    if alg == "madsbo":
        return cached_jit(
            cache, ckey,
            lambda: lambda st, al, ah: baseline_masked_round(
                alg, st, al, ah, problem=problem, topo=topo, cfg=cfg,
                depth=depth, damping=damping, decay=decay,
            ),
        )
    return cached_jit(
        cache, ckey,
        lambda: lambda st, al: baseline_masked_round(
            alg, st, al, problem=problem, topo=topo, cfg=cfg,
            depth=depth, damping=damping, decay=decay,
        ),
    )
