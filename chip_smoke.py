#!/usr/bin/env python3
"""Chip smoke test: C2DFB at phi3-mini widths on a TPU, through `c2dfb.run`.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chip   # four chips, executed DeviceTransport

One chip.  Each Pallas kernel (block top-k and quantize in f32 and bf16,
sparse pack and unpack), compiled with Mosaic, must agree with its jnp
oracle in `repro.kernels.ref` on the chip.  Then the LM
hyper-representation problem (`repro.core.lm_bilevel`: backbone upper,
head lower) at the published phi3-mini widths (d_model 3072, 32 x 96
heads, d_ff 8192, vocab 32064), depth cut to ``LAYERS``, runs through
`repro.core.c2dfb.run` on ``ring(NODES)`` with the compiled Pallas block
top-k compressor.  Each run of ``T`` rounds is made twice: the first
gives the compile seconds, the second the seconds per round (its time
after its last compile step, from JAX's monitoring events, over ``T``).
The hypergradient norm and consensus errors of every round must be
finite, and the lowered round must hold the Pallas kernel
(``tpu_custom_call``).  The jnp block top-k (`jax.lax.top_k`) is the
reference: on the run's own next transmissions at full head width the
two compressors must select the same survivors (``array_equal``), and
each compressor call is timed there.  (A whole run with the jnp
compressor does not fit one chip at depth 2: its scan needs 6.85 GiB
of temporaries on top of the state.)

Four chips (``--four-chip``, this phase only).  One node per chip on
``ring(4)``: `DeviceTransport(fused=True)` (on-device compression and
packing, collectives move packed records) against
`DeviceTransport(fused=False)` (dense collectives) on the same LM
problem at depth ``FOUR_LAYERS``.  Their trajectories must be bit-identical, every executed inner
message's bytes must equal `wire.measure_tree_bytes_chunked`, and each
node's state must live on its own chip.

JAX's persistent compile cache is placed by
`repro.launch.compile_cache.enable_compile_cache`.  The script needs a
TPU: on any other platform it exits 1 without a result.  Its last line of
standard output is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.phi3_mini_3p8b import CONFIG as PHI3_MINI  # noqa: E402
from repro.core.c2dfb import C2DFBConfig, c2dfb_round, run  # noqa: E402
from repro.core.inner_loop import inner_transmit  # noqa: E402
from repro.core.lm_bilevel import init_node_params, make_lm_bilevel  # noqa: E402
from repro.core.topology import ring  # noqa: E402
from repro.data.synthetic import node_streams  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.pack_residuals import (  # noqa: E402
    pack_sparse_blocks,
    padded_k,
    unpack_sparse_blocks,
)
from repro.kernels.quantize import quantize_pallas  # noqa: E402
from repro.kernels.topk_compress import block_topk_pallas  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

# one chip: two phi3-mini nodes at depth 2 (325M backbone + 98.5M head
# parameters each, bf16); depth 1 peaked at 9.4 GB of the v5e's 16.9 GB
LAYERS = 2
NODES = 2
BATCH, SEQ = 1, 1024
T, K = 3, 3
SEED = 0
# timed calls of each compressor on a residual (median reported)
REPS = 5

# four chips: one node per chip; one round of K=1 at depth 1, since the
# host meters every message of both runs and its dense outer payloads
# scale with the backbone (on a 2x2 v5e host, 122-167 s of host metering
# per round at depth 2)
FOUR_LAYERS = 1
FOUR_NODES = 4
FOUR_T, FOUR_K = 1, 1
FOUR_CHUNK = 1 << 20

COMPILE_EVENTS = "/jax/core/compile/"
COMPILE_EVENT = COMPILE_EVENTS + "backend_compile_duration"


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(*parts) -> None:
    print(*parts, flush=True)


# ---------------------------------------------------------------------------
# problem
# ---------------------------------------------------------------------------


def c2dfb_config(K: int, compressor: str = "kernel_topk") -> C2DFBConfig:
    return C2DFBConfig(
        lam=10.0, eta_out=1e-3, gamma_out=0.5, eta_in=0.06, gamma_in=0.5,
        K=K, compressor=compressor, comp_ratio=0.1, comp_block=1024,
    )


def node_data(mcfg, m: int, seed: int) -> dict:
    batches = [
        s.next_batch()
        for s in node_streams(m, mcfg.vocab_size, SEQ, BATCH, seed=seed)
    ]
    return {
        k: jnp.asarray(np.stack([b[k] for b in batches]))
        for k in ("tokens", "labels")
    }


def build(mcfg, m: int):
    """The LM bilevel problem on ``m`` nodes, weights and data from SEED."""
    problem = make_lm_bilevel(
        mcfg, node_data(mcfg, m, SEED + 1), node_data(mcfg, m, SEED + 2), m
    )
    x0, y0 = init_node_params(mcfg, jax.random.PRNGKey(SEED), m)
    return problem, x0, y0


def per_node_count(tree, m: int) -> int:
    return sum(int(v.size) for v in jax.tree.leaves(tree)) // m


# ---------------------------------------------------------------------------
# kernels vs their oracles, compiled on the chip
# ---------------------------------------------------------------------------


def kernel_checks(nb: int = 16, block: int = 1024) -> None:
    key = jax.random.PRNGKey(SEED)
    k = max(1, round(0.1 * block))
    for dtype in (jnp.float32, jnp.bfloat16):
        name = jnp.dtype(dtype).name
        x = jax.random.normal(key, (nb, block), dtype)
        got = block_topk_pallas(x, k=k, block=block, interpret=False)
        want = ref.block_topk_ref(x, k)
        check(
            np.array_equal(np.asarray(got, np.float32),
                           np.asarray(want, np.float32)),
            f"block_topk_pallas[{name}] disagrees with block_topk_ref",
        )
        u = jax.random.uniform(jax.random.PRNGKey(SEED + 1), (nb, block), dtype)
        gq, gs = quantize_pallas(x, u, bits=4, block=block, interpret=False)
        wq, ws = ref.quantize_ref(x, u, 4)
        dq = float(np.max(np.abs(
            np.asarray(gq, np.float32) - np.asarray(wq, np.float32)
        )))
        ds = float(np.max(np.abs(
            np.asarray(gs, np.float32) - np.asarray(ws, np.float32)
        )))
        say(f"kernel quantize[{name}]: max |kernel - ref| values {dq!r} "
            f"scales {ds!r}")
        check(
            dq <= 1e-6 and ds <= 1e-7,
            f"quantize_pallas[{name}] disagrees with quantize_ref",
        )
        kept = int(np.count_nonzero(np.asarray(got, np.float32)))
        say(f"kernel block_topk[{name}]: exact match, {kept} kept of "
            f"{nb * block}")
    tiles = ref.block_topk_ref(jax.random.normal(key, (nb, block)), k)
    kpad = padded_k(k)
    vals, idx = pack_sparse_blocks(tiles, k=k, block=block, interpret=False)
    wv, wi = ref.pack_ref(tiles, kpad)
    check(
        np.array_equal(np.asarray(vals), np.asarray(wv))
        and np.array_equal(np.asarray(idx), np.asarray(wi)),
        "pack_sparse_blocks disagrees with pack_ref",
    )
    back = unpack_sparse_blocks(vals, idx, block=block, interpret=False)
    check(
        np.array_equal(np.asarray(back), np.asarray(ref.unpack_ref(wv, wi, block)))
        and np.array_equal(np.asarray(back), np.asarray(tiles)),
        "unpack_sparse_blocks disagrees with unpack_ref",
    )
    say("kernel pack/unpack: exact match")


# ---------------------------------------------------------------------------
# one chip: c2dfb.run
# ---------------------------------------------------------------------------


class CompileTimer:
    """Reads JAX's own monitoring events: ``seconds`` sums XLA backend
    compiles (compile-cache loads included), ``last_end`` is the wall time
    at which the latest trace / lower / compile step ended."""

    def __init__(self):
        self.seconds = 0.0
        self.last_end = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_time_span_listener(self._span)

    def _duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration

    def _span(self, event, start, end, **_):
        if event.startswith(COMPILE_EVENTS):
            self.last_end = max(self.last_end, end)


def timed_run(problem, topo, cfg, x0, y0, key, timer):
    """Two runs of ``T`` rounds through `run`, cold then warm.  Returns the
    warm run's state and metrics, the cold run's backend-compile seconds
    and the warm run's seconds per round: its time after its last compile
    step (the scan's dispatch and execution), over ``T``."""
    for attempt in ("cold", "warm"):
        c0 = timer.seconds
        t0 = time.time()
        state, mets = run(problem, topo, cfg, x0, y0, T=T, key=key)
        jax.block_until_ready((state, mets))
        t1 = time.time()
        compile_s = timer.seconds - c0
        exec_s = t1 - max(timer.last_end, t0)
        say(f"{cfg.compressor} run T={T} {attempt}: wall {t1 - t0!r} s, "
            f"backend compile {compile_s!r} s, after the last compile "
            f"{exec_s!r} s")
        if attempt == "cold":
            cold_compile = compile_s
            del state, mets
    return state, mets, cold_compile, exec_s / T


def one_chip(mcfg=None, m: int = NODES) -> None:
    mcfg = mcfg or dataclasses.replace(PHI3_MINI, num_layers=LAYERS)
    problem, x0, y0 = build(mcfg, m)
    topo = ring(m)
    cfg = c2dfb_config(K)
    key = jax.random.PRNGKey(SEED)
    say(f"model {mcfg.name} layers={mcfg.num_layers} d_model={mcfg.d_model} "
        f"heads={mcfg.num_heads}x{mcfg.head_dim} d_ff={mcfg.d_ff} "
        f"vocab={mcfg.vocab_size} batch={BATCH} seq={SEQ}")
    say(f"params per node: backbone (x) {per_node_count(x0, m)} head (y) "
        f"{per_node_count(y0, m)}; nodes {m} on ring({m}); K={K} "
        f"compressor={cfg.compressor}")

    timer = CompileTimer()
    state, mets, compile_s, per_round = timed_run(
        problem, topo, cfg, x0, y0, key, timer
    )
    say(f"compile seconds (first run, T={T}): {compile_s!r}")
    say(f"seconds per round after warm-up: {per_round!r}")
    check(per_round > 0, "per-round time is not positive")

    host = {k: np.asarray(v) for k, v in mets.items()}
    for t in range(T):
        say(f"round {t}: hypergrad_norm {host['hypergrad_norm'][t]!r} "
            f"x_consensus {host['x_consensus_err'][t]!r} "
            f"y_consensus {host['y_consensus_err'][t]!r} "
            f"z_consensus {host['z_consensus_err'][t]!r}")
    for name in ("hypergrad_norm", "x_consensus_err", "y_consensus_err",
                 "z_consensus_err"):
        check(np.all(np.isfinite(host[name])), f"{name} is not finite")
    check(
        all(bool(jnp.all(jnp.isfinite(v))) for v in jax.tree.leaves(state.x)),
        "final upper-level parameters are not finite",
    )

    lowered = jax.jit(
        lambda st, k_: c2dfb_round(st, k_, problem, topo, cfg)
    ).lower(state, key)
    check(
        "tpu_custom_call" in lowered.as_text(),
        "the lowered C2DFB round holds no compiled Pallas kernel",
    )
    say("lowered round: tpu_custom_call present")
    stats = jax.devices()[0].memory_stats() or {}
    say(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')!r} of "
        f"bytes_limit {stats.get('bytes_limit')!r}")

    compressor_checks(state, key, (cfg, c2dfb_config(K, "block_topk")))


def compressor_checks(state, key, cfgs) -> None:
    """The kernel and the jnp block top-k on the run's own next
    transmissions (each inner loop's d and s residuals, full head width):
    both must keep exactly the same survivors.  Each call is timed warm,
    the median of ``REPS``."""
    fns = {
        c.compressor: jax.jit(
            functools.partial(inner_transmit, c.make_compressor())
        )
        for c in cfgs
    }
    for name, inner in (("y", state.inner_y), ("z", state.inner_z)):
        for part, value, refpt in (("d", inner.d, inner.d_hat),
                                   ("s", inner.s, inner.s_hat)):
            outs, secs = {}, {}
            for comp, fn in fns.items():
                outs[comp] = jax.block_until_ready(fn(key, value, refpt))
                times = []
                for _ in range(REPS):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(key, value, refpt))
                    times.append(time.perf_counter() - t0)
                secs[comp] = float(np.median(times))
            got, want = outs.values()
            same = all(
                np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))
            )
            kept = sum(int(jnp.count_nonzero(v)) for v in jax.tree.leaves(got))
            size = sum(int(v.size) for v in jax.tree.leaves(got))
            say(f"compressor {name}.{part} residual: "
                f"{' == '.join(fns)} {same}; {kept} kept of {size}; "
                + "; ".join(f"{c} {t!r} s per call" for c, t in secs.items()))
            check(same, f"{' and '.join(fns)} select differently on the "
                  f"{name}.{part} residual")


# ---------------------------------------------------------------------------
# four chips: fused vs dense DeviceTransport
# ---------------------------------------------------------------------------


def four_chip(mcfg=None, m: int = FOUR_NODES) -> None:
    from repro.net.wire import measure_tree_bytes_chunked
    from repro.transport import DeviceTransport
    from repro.transport.device import mesh_for_nodes
    from repro.transport.engine import run_c2dfb_transport

    mcfg = mcfg or dataclasses.replace(PHI3_MINI, num_layers=FOUR_LAYERS)
    check(len(jax.devices()) >= m, f"needs {m} devices, has {len(jax.devices())}")
    mesh = mesh_for_nodes(m)
    problem, x0, y0 = build(mcfg, m)
    topo = ring(m)
    cfg = c2dfb_config(FOUR_K)
    comp = cfg.make_compressor()
    key = jax.random.PRNGKey(SEED)
    say(f"four-chip: {mcfg.name} layers={mcfg.num_layers} nodes {m} on "
        f"ring({m}), T={FOUR_T} K={FOUR_K} chunk={FOUR_CHUNK}")

    runs = {}
    for name, fused in (("fused", True), ("dense", False)):
        t0 = time.perf_counter()
        st, mets = run_c2dfb_transport(
            problem, topo, cfg, x0, y0, FOUR_T, key,
            DeviceTransport(mesh=mesh, fused=fused, chunk=FOUR_CHUNK),
            return_payloads=True,
        )
        jax.block_until_ready(st)
        say(f"{name}: total {time.perf_counter() - t0!r} s; device round "
            f"s {[float(w) for w in mets['wall_seconds']]}; host meter_round "
            f"s {[float(w) for w in mets['meter_seconds']]}")
        for leaf in jax.tree.leaves(st):
            if leaf.ndim == 0:
                continue
            shards = leaf.addressable_shards
            check(
                len({s.device for s in shards}) == m
                and all(s.data.shape[0] == 1 for s in shards),
                f"{name}: a node-stacked state leaf is not one node per chip",
            )
        # the state moves to the host so the next run has the chips' memory
        runs[name] = (
            jax.tree.map(np.asarray, (st.x, st.s_x, st.inner_y.d, st.inner_z.d)),
            mets,
        )
        del st
    (st_f, met_f), (st_d, met_d) = runs["fused"], runs["dense"]
    say("placement: every state leaf holds one node on each of "
        f"{m} chips")

    same = {
        part: all(
            np.array_equal(a, b)
            for a, b in zip(jax.tree.leaves(f), jax.tree.leaves(d))
        )
        for part, f, d in zip(("x", "s_x", "y", "z"), st_f, st_d)
    }
    say(f"fused == dense, per state part: {same}")
    check(all(same.values()),
          "fused and dense trajectories are not bit-identical")
    for name in ("hypergrad_norm", "x_consensus_err", "y_consensus_err"):
        check(np.all(np.isfinite(np.asarray(met_f[name]))),
              f"fused {name} is not finite")

    checked = 0
    for t in range(FOUR_T):
        nb_f = met_f["payloads"][t]["node_bytes"]
        nb_d = met_d["payloads"][t]["node_bytes"]
        for tag in ("y", "z"):
            q_d, q_s = met_d["payloads"][t][tag]
            for k in range(FOUR_K):
                for part, stack in (("d", q_d), ("s", q_s)):
                    label = f"{tag}/in{k}/{part}"
                    for i in range(m):
                        slc = [np.asarray(v)[k, i] for v in jax.tree.leaves(stack)]
                        want = measure_tree_bytes_chunked(comp, slc, FOUR_CHUNK)
                        check(
                            nb_f[label][i] == nb_d[label][i] == want,
                            f"round {t} {label} node {i}: executed bytes "
                            f"fused {nb_f[label][i]} dense {nb_d[label][i]} "
                            f"codec {want}",
                        )
                        checked += 1
    check(
        np.array_equal(np.asarray(met_f["wire_bytes"]),
                       np.asarray(met_d["wire_bytes"])),
        "fused and dense wire bytes differ",
    )
    say(f"executed bytes == measure_tree_bytes_chunked for {checked} inner "
        f"messages; wire bytes per round {np.asarray(met_f['wire_bytes']).tolist()}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-chip DeviceTransport phase")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    say(f"compile cache: {enable_compile_cache()}")
    try:
        if args.four_chip:
            four_chip()
        else:
            kernel_checks()
            one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
