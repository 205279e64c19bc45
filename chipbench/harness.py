"""One run of one cell: set-up, the measured window, the check against
the plain reference, and the result line.

The window drives the program's own round, `repro.core.c2dfb.c2dfb_round`,
jitted once with its state donated; set-up makes the weights and the
program's initial state in one jitted call from the seed, and runs the
first ``check_steps`` rounds through the same call (the first compiles or
loads it from the cache).  Their readings are compared with the plain
reference's after the window has closed and the program's state is
freed (`chipbench.check`).

A traced run profiles a few rounds and splits their device time by the
program's named scopes and the cell's kernels (`chipbench.scopes`), as
the cell's per-layer metric readers declare them (``SCOPE``, ``KERNEL``;
`chipbench.metrics`).
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import check, kinds, peaks, scopes, tokens, trace_reduce, weights
from chipbench.reference.c2dfb import Reference
from chipbench.reference.topology import gossip_matrix
from repro.configs.base import ModelConfig
from repro.core.c2dfb import C2DFBConfig, c2dfb_round, init_state
from repro.core.lm_bilevel import make_lm_bilevel
from repro.core.topology import ring

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TOPOLOGIES = {"ring": ring}
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    model: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfile = json.loads((root / conf["file"]).read_text())
    model = cfile["model"]
    if kinds.KEY in cfile:  # the kind map sits beside the model in the file
        model = dict(model, **{kinds.KEY: cfile[kinds.KEY]})
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((BENCH / "workloads" / f"{name}.json").read_text())["limits"]
    mine = lambda m: name in m.get("workloads", [name])
    return Cell(name, w["chips"], model, traffic, limits,
                [m for m in bench["end_to_end"] if mine(m)],
                [m for m in bench["per_layer"] if mine(m)])


def model_config(mc: dict) -> ModelConfig:
    """The program's configuration; the benchmark's kind map stays out."""
    kw = {k: v for k, v in mc.items() if k != kinds.KEY}
    return ModelConfig(**dict(kw, dtype=jnp.dtype(mc["dtype"]), pattern=tuple(mc["pattern"])))


def c2dfb_config(tr: dict) -> C2DFBConfig:
    return C2DFBConfig(
        lam=tr["lam"], eta_out=tr["eta_out"], gamma_out=tr["gamma_out"],
        eta_in=tr["eta_in"], gamma_in=tr["gamma_in"], K=tr["K"],
        compressor=tr["compressor"], comp_ratio=tr["comp_ratio"],
        comp_bits=tr["comp_bits"], comp_block=tr["comp_block"])


def enable_compile_cache() -> str:
    """The persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when set,
    else ``<checkout>/.jax_cache``; every program is cached.  The key
    includes the programs' metadata, so an entry compiled from a tree
    without the named scopes, whose ``op_name``s lack them, never comes
    back in place of one that has them (traced and untraced runs share
    the entries)."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return d


def say(*parts):
    print(*parts, file=sys.stderr, flush=True)


def _outer_bytes(mc: dict, m: int) -> int:
    """Dense outer bytes of one round: x and s_x as f32 from every node."""
    x, _ = weights.split(weights.leaf_specs(mc))
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(x, is_leaf=weights.is_leaf))
    return 2 * n * 4 * m


def _head_sizes(mc: dict) -> list:
    _, y = weights.split(weights.leaf_specs(mc))
    return [int(np.prod(s.shape)) for s in jax.tree.leaves(y, is_leaf=weights.is_leaf)]


def build(cell: Cell, seed: int) -> SimpleNamespace:
    """The program's jitted initial state and round, the data and keys of
    ``seed``."""
    mc, tr = cell.model, cell.traffic
    m = tr["nodes"]
    mcfg, cfg = model_config(mc), c2dfb_config(tr)
    topo = TOPOLOGIES[tr["topology"]](m)
    key = weights.seed_key(seed)
    rkey0 = jax.random.fold_in(key, 2)
    data_g = tokens.node_data(mc["vocab_size"], tr, seed, 0)
    data_f = tokens.node_data(mc["vocab_size"], tr, seed, 1)

    def problem(df, dg):
        return make_lm_bilevel(mcfg, dg, df, m, ridge=tr["ridge"])

    return SimpleNamespace(
        cell=cell, m=m, T=tr["check_steps"], topo=topo,
        wkey=jax.random.fold_in(key, 1),
        rkey=lambda t: jax.random.fold_in(rkey0, t),
        data_f=data_f, data_g=data_g,
        df={k: jnp.asarray(v) for k, v in data_f.items()},
        dg={k: jnp.asarray(v) for k, v in data_g.items()},
        init_fn=jax.jit(lambda key, df, dg: init_state(
            problem(df, dg), cfg, *weights.make_xy(mc, key, m))),
        round_fn=jax.jit(
            lambda st, key, df, dg: c2dfb_round(st, key, problem(df, dg), topo, cfg),
            donate_argnums=0),
    )


def program_check(b: SimpleNamespace):
    """The program's initial state and its first ``check_steps`` rounds
    through the window's own call; returns (state, round metrics,
    readings)."""
    state = b.init_fn(b.wkey, b.df, b.dg)
    prog = check.Readings(check.hat_view(check.program_view(state)))
    mets = []
    for t in range(1, b.T + 1):
        state, met = b.round_fn(state, b.rkey(t), b.df, b.dg)
        mets.append(met)
        prog.take(check.program_view(state), t, b.T, b.cell.model, b.wkey, b.m)
    return state, mets, prog


def reference_check(b: SimpleNamespace, precision: str = "f32", W=None,
                    data_f=None, data_g=None) -> dict:
    """The plain reference's readings over the same steps, with its own
    gossip matrix; ``precision``, ``W`` and the data can be changed to
    read the control and faults."""
    mc, tr = b.cell.model, b.cell.traffic
    ref = Reference(mc, tr, b.data_f if data_f is None else data_f,
                    b.data_g if data_g is None else data_g,
                    gossip_matrix(tr) if W is None else W, precision)
    rs = ref.init(b.wkey)
    out = check.Readings(check.hat_view(check.reference_view(rs)))
    for t in range(1, b.T + 1):
        rs = ref.round(rs)
        out.take(check.reference_view(rs), t, b.T, mc, b.wkey, b.m)
    return out.as_dict()


def find_chips(n: int):
    """JAX's devices and their published peaks; `NoChip` where it finds
    fewer than ``n`` TPU chips."""
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(f"the cell needs {n} TPU chip(s); JAX found {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return devs, peaks.peaks(devs[0].device_kind)


def declared(per_layer: list) -> tuple[list, list]:
    """The program scopes and the kernels (modules of `chipbench.kernels`)
    that the readers of ``per_layer`` declare as ``SCOPE`` / ``KERNEL``."""
    scope_names, kernel_names = [], []
    for spec in per_layer:
        reader = importlib.import_module(f"chipbench.metrics.{spec['name']}")
        if hasattr(reader, "SCOPE"):
            scope_names.append(reader.SCOPE)
        if hasattr(reader, "KERNEL"):
            kernel_names.append(reader.KERNEL)
    return scope_names, kernel_names


def read_trace(pd, hlo_text: str, compressor: str, per_layer: list):
    """One traced window: `trace_reduce.reduce` over the compressor's
    kernel (as ``compress``) and each declared kernel (by its module
    name), and `scopes.read`'s partition over the declared scopes and
    every one of those kernels, labelled by module name (None where the
    program carries no scopes)."""
    scope_names, kernel_names = declared(per_layer)
    pattern = {k: importlib.import_module(f"chipbench.kernels.{k}").PATTERN
               for k in dict.fromkeys([compressor, *kernel_names])}
    reduced = trace_reduce.reduce(
        pd, {"compress": pattern[compressor], **{k: pattern[k] for k in kernel_names}})
    return reduced, scopes.read(pd, hlo_text, pattern, scope_names)


def _say_partition(part, reduced, rounds: int):
    if part is None:
        say("scopes: the traced program carries none")
        return
    ms = {k: 1e3 * v / rounds for k, v in part["scope_s"].items()}
    say("scopes, ms a round: " + ", ".join(f"{k} {v!r}" for k, v in ms.items()))
    say(f"scopes: sum {sum(ms.values())!r} against busy "
        f"{1e3 * reduced['busy_s'] / rounds!r} ms a round; in fusions of more than "
        f"one scope {1e3 * part['mixed_s'] / rounds!r} ms a round")


@contextlib.contextmanager
def compiles_counted():
    """The durations of the programs that JAX compiles, or loads from the
    persistent cache, inside the block (its backend compile events)."""
    got = []

    def listen(event, duration, **_):
        if event == BACKEND_COMPILE:
            got.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield got
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float) -> dict:
    devs, pk = find_chips(cell.chips)
    platform, kind = devs[0].platform, devs[0].device_kind
    mc, tr = cell.model, cell.traffic
    m, T = tr["nodes"], tr["check_steps"]

    # ---- set-up: weights and state, then the check steps --------------------
    b = build(cell, seed)
    round_fn, rkey, df, dg = b.round_fn, b.rkey, b.df, b.dg
    state, mets, prog = program_check(b)
    setup_s = time.time() - t_start
    say(f"setup_s {setup_s!r} (weights, initial state, {T} check rounds); "
        f"peak_bytes_in_use so far {(devs[0].memory_stats() or {}).get('peak_bytes_in_use')!r}")

    # ---- the window ---------------------------------------------------------
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans only: less overhead
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    window_mets = []
    t, prev = T, None
    t0 = time.perf_counter()
    with compiles_counted() as compiles, jax.profiler.TraceAnnotation("window"):
        while True:
            t += 1
            with jax.profiler.TraceAnnotation("enqueue"):
                state, met = round_fn(state, rkey(t), df, dg)
            window_mets.append(met)
            if prev is not None:
                with jax.profiler.TraceAnnotation("wait"):
                    jax.block_until_ready(prev)
            prev = met
            if trace and len(window_mets) >= tr["trace_rounds"]:
                break
            if not trace and time.perf_counter() - t0 >= seconds:
                break
        with jax.profiler.TraceAnnotation("wait"):
            jax.block_until_ready(prev)
    window_s = time.perf_counter() - t0
    rounds = len(window_mets)
    reduced = part = None
    if trace:
        jax.profiler.stop_trace()
    say(f"window: {rounds} rounds in {window_s!r} s")
    say(f"window_compiles {len(compiles)}")

    host = jax.device_get(mets + window_mets)
    # ``peak_bytes_in_use`` counts the buffers (state, data, outputs); the
    # compiled programs' temporaries are reserved apart, in
    # ``peak_bytes_reserved``: the chip's peak is their sum.
    stats = devs[0].memory_stats() or {}
    peak_bytes = stats.get("peak_bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0)
    say(f"memory: {stats!r}")
    if trace:  # the window's own executable, from the in-memory cache
        hlo_text = round_fn.lower(state, rkey(t), df, dg).compile().as_text()
    del state, met, prev, mets, window_mets

    if trace:
        pd = trace_reduce.load(trace_reduce.find_trace(trace_dir))
        reduced, part = read_trace(pd, hlo_text, tr["compressor"], cell.per_layer)
        del pd, hlo_text
        shutil.rmtree(trace_dir, ignore_errors=True)
        _say_partition(part, reduced, rounds)

    # ---- the check: the plain reference over the same steps -----------------
    r0 = time.perf_counter()
    ref = reference_check(b)
    say(f"reference: {time.perf_counter() - r0!r} s")
    gaps = check.compare(prog.as_dict(), ref)
    correct, shown = check.verdict(gaps, cell.limits)

    inner = [int(h["inner_bytes"]) for h in host[T:]]
    failed = sum(not np.isfinite(h["hypergrad_norm"]) for h in host)
    ctx = SimpleNamespace(
        cell=cell, model=mc, traffic=tr, chips=cell.chips, peaks=pk,
        setup_s=setup_s, window_s=window_s, rounds=rounds, inner_bytes=inner,
        outer_bytes=_outer_bytes(mc, m), peak_bytes=peak_bytes, trace=reduced,
        scope_s=None if part is None else part["scope_s"],
        head_sizes=_head_sizes(mc), itemsize=jnp.dtype(mc["dtype"]).itemsize)
    metrics = {}
    for spec in (cell.per_layer if trace else cell.end_to_end):
        reader = importlib.import_module(f"chipbench.metrics.{spec['name']}")
        value = reader.read(ctx)
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    device = {"platform": platform, "kind": kind, "count": cell.chips,
              "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": T + rounds, "failed": int(failed),
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["check"] = {k: {"value": v["value"], "limit": v["limit"]}
                       for k, v in shown.items() if v["limit"] is not None}
    for k, v in sorted(shown.items(), key=lambda kv: kv[1]["limit"] is not None):
        what = "not compared" if v["limit"] is None else f"limit {v['limit']!r}"
        say(f"check {k}: {v['value']!r} (worst at {v['worst']}) {what}")
    return result
