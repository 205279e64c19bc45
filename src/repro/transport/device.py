"""`DeviceTransport` — in-process multi-device execution of the gossip
protocol over a `jax.sharding.Mesh`.

Where `SimTransport` prices phases on a simulated wire, this backend RUNS
them: each bilevel node lives on its own mesh device (CPU works via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``), and every gossip
exchange is a real `shard_map` collective — `lax.ppermute` neighbor
shifts for shift-structured topologies (ring / two-hop / torus, the
ICI-native pattern), `lax.all_gather` for general graphs.  Crucially the
tensors crossing rank boundaries are the protocol's ACTUAL wire payloads:
the compressed residuals of Algorithm 2's reference-point exchanges (and
the dense x / s_x outer broadcasts), not the dense state a plain SPMD
simulation would move.

Wire truth: every executed payload additionally makes the
`repro.net.wire` encode -> transfer -> decode round trip per edge on the
host (`meter_round` / `exchange`), so byte counts are integers produced
by running codec code on the real messages — `wire.measure_tree_bytes`
exactly, asserted in tests — and the codec's bit-exact delivery is
verified message-for-message (KernelQuant's fused dequant is 1-ulp, see
`repro.net.wire`).

Parity contract (tests/test_transport.py): a full C2DFB run through
`make_device_round` reproduces the sequential node-stacked simulator
within fp32 tolerance — the compressor randomness is drawn IDENTICALLY
(`_compress_rank` mirrors `inner_loop.compress_stacked`'s key derivation
split-for-split), so the only divergence is floating-point reduction
order between the row-wise collective mix and the dense matmul mix.

Reference copies: each rank keeps live copies of its neighbors'
reference points, updated only by received residuals — the deployment
data structure.  Copies are (re)materialized from the current references
at round start with one collective (a setup sync, not charged to the
per-round wire accounting, which counts exactly the protocol's
2 dense outer + 2K compressed inner messages — `c2dfb.round_phases`).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import compression as C
from repro.core.bilevel_problem import BilevelProblem
from repro.core.compression import Compressor
from repro.core.inner_loop import InnerState, refresh_tracker
from repro.core.topology import Topology
from repro.core.types import Pytree
from repro.kernels.pack_residuals import (
    pack_sparse_blocks,
    padded_k,
    unpack_sparse_blocks,
)
from repro.net import wire
from repro.net.fabric import NetworkFabric, StragglerModel
from repro.net.wire import codec_for
from repro.transport.base import ExchangeReport, Transport


def mesh_for_nodes(m: int, axis: str = "nodes") -> Mesh:
    """A 1-D mesh of the first ``m`` local devices (one bilevel node per
    device).  On CPU, export ``XLA_FLAGS=--xla_force_host_platform_
    device_count=N`` BEFORE importing jax to get N virtual devices."""
    devs = jax.devices()
    if len(devs) < m:
        raise ValueError(
            f"DeviceTransport needs {m} devices for {m} nodes but only "
            f"{len(devs)} are visible — on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={m} before importing "
            "jax (the transport-parity CI job does exactly this)"
        )
    return Mesh(np.array(devs[:m]), (axis,))


def _compress_rank(
    compressor: Compressor, key: jax.Array, tree: Pytree, rank, m: int
) -> Pytree:
    """Per-rank twin of `inner_loop.compress_stacked`: identical key
    derivation (split per leaf, then per node; this rank uses row
    ``rank``), applied to this rank's axis-1 slice — so device and
    simulator draw bit-identical compressor randomness."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, leaf in zip(keys, leaves):
        node_keys = jax.random.split(k, m)
        out.append(compressor(node_keys[rank], leaf[0])[None])
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# fused on-device compression: packed (vals, idx) record form on the wire
# ---------------------------------------------------------------------------


def fused_pack_spec(compressor: Compressor) -> tuple[int, int]:
    """(block, kpad) of the fused packed exchange, or raise for compressors
    whose residuals are not block-sparse tiles.  ``kpad`` is the per-block
    record budget (k rounded up to the 128-lane boundary), so the packed
    form moves ``nb * kpad * 8`` bytes per leaf where the dense tile form
    moves ``nb * block * 4`` — a 2*kpad/block exchange-size ratio."""
    if not isinstance(compressor, (C.BlockTopK, C.KernelBlockTopK)):
        raise ValueError(
            "fused on-device compression needs a block-sparse compressor "
            "(block_topk / kernel_topk) whose survivors fit the packed "
            f"(vals, idx) record form; got {type(compressor).__name__}"
        )
    block = compressor.block
    k = max(1, int(round(compressor.ratio * block)))
    return block, padded_k(k)


def _pack_tree(tree: Pytree, block: int, kpad: int) -> tuple[Pytree, Pytree]:
    """Per-rank residual tree (leaves (1, *shape)) -> packed record trees
    ``(vals, idx)`` with leaves (1, nb, kpad) — the Pallas pack kernel run
    ON-DEVICE inside shard_map, so the wire collectives move records, never
    dense tiles."""
    leaves, treedef = jax.tree.flatten(tree)
    vs, ix = [], []
    for leaf in leaves:
        flat = leaf[0].reshape(-1).astype(jnp.float32)
        d = flat.shape[0]
        nb = -(-d // block)
        tiles = jnp.pad(flat, (0, nb * block - d)).reshape(nb, block)
        vals, idx = pack_sparse_blocks(tiles, k=kpad, block=block)
        vs.append(vals[None])
        ix.append(idx[None])
    return jax.tree.unflatten(treedef, vs), jax.tree.unflatten(treedef, ix)


def _unpack_like(
    vals_tree: Pytree, idx_tree: Pytree, like: Pytree, block: int
) -> Pytree:
    """Inverse of `_pack_tree` against a shape/dtype template: packed
    leaves (..., nb, kpad) -> dense leaves shaped/typed like ``like``
    (leading node axis preserved).  Bit-exact for <= kpad survivors per
    block: the one-hot f32 routing moves values untouched, and
    f32 -> leaf-dtype is exact for values that started in that dtype."""

    def leaf(v, i, l):
        nb, kpad = v.shape[-2:]
        lead = int(np.prod(l.shape[:1]))
        d = int(np.prod(l.shape[1:]))
        dense = unpack_sparse_blocks(
            v.reshape(-1, kpad), i.reshape(-1, kpad), block
        )
        return (
            dense.reshape(lead, nb * block)[:, :d]
            .reshape(l.shape)
            .astype(l.dtype)
        )

    return jax.tree.map(leaf, vals_tree, idx_tree, like)


# ---------------------------------------------------------------------------
# collective gossip engines (per-rank, inside shard_map)
# ---------------------------------------------------------------------------


class _PpermuteGossiper:
    """Neighbor-copy exchange for shift-structured topologies: rank r
    keeps one copy per schedule shift (the reference of node r - shift),
    refreshed by `lax.ppermute` of the broadcast residuals."""

    def __init__(self, topo: Topology, axis: str):
        self.axis = axis
        self.m = topo.m
        self.schedule = topo.ppermute_schedule

    def _perm(self, shift: int):
        m = self.m
        return [((r - shift) % m, r) for r in range(m)]  # receive from r-shift

    def _shift(self, tree: Pytree, shift: int) -> Pytree:
        perm = self._perm(shift)
        return jax.tree.map(
            lambda v: jax.lax.ppermute(v, self.axis, perm), tree
        )

    def init(self, value: Pytree) -> tuple:
        return tuple(self._shift(value, s) for s, _ in self.schedule)

    def mix(self, copies: tuple, own: Pytree, rank) -> Pytree:
        def leaf(o, *cs):
            acc = jnp.zeros_like(o, dtype=jnp.float32)
            for (_, w), c in zip(self.schedule, cs):
                acc = acc + jnp.float32(w) * (
                    c.astype(jnp.float32) - o.astype(jnp.float32)
                )
            return acc.astype(o.dtype)

        return jax.tree.map(leaf, own, *copies)

    def push(self, copies: tuple, q_own: Pytree) -> tuple:
        return tuple(
            jax.tree.map(jnp.add, c, self._shift(q_own, s))
            for (s, _), c in zip(self.schedule, copies)
        )

    def push_packed(self, copies: tuple, packed, block: int) -> tuple:
        """Fused push: `lax.ppermute` moves the packed (vals, idx) records
        — nb*kpad*8 bytes per leaf, not the nb*block*4 dense tile — and
        each receiver unpacks on its own device."""
        vals_t, idx_t = packed
        out = []
        for (s, _), c in zip(self.schedule, copies):
            q = _unpack_like(
                self._shift(vals_t, s), self._shift(idx_t, s), c, block
            )
            out.append(jax.tree.map(jnp.add, c, q))
        return tuple(out)


class _AllGatherGossiper:
    """General-graph fallback: rank r keeps the full reference table
    (m, ...) updated by all-gathered residual broadcasts; mixing is this
    rank's row of W - I against the table (same arithmetic as
    `gossip.mix_delta_dense`, one row at a time)."""

    def __init__(self, topo: Topology, axis: str):
        self.axis = axis
        self.m = topo.m
        self.W = jnp.asarray(topo.W, jnp.float32)

    def _gather(self, tree: Pytree) -> Pytree:
        return jax.tree.map(
            lambda v: jax.lax.all_gather(v[0], self.axis), tree
        )

    def init(self, value: Pytree) -> Pytree:
        return self._gather(value)

    def mix(self, table: Pytree, own: Pytree, rank) -> Pytree:
        row = self.W[rank] - jax.nn.one_hot(rank, self.m, dtype=jnp.float32)

        def leaf(t, o):
            flat = t.reshape(self.m, -1).astype(jnp.float32)
            out = row @ flat
            return out.reshape(o.shape[1:]).astype(o.dtype)[None]

        return jax.tree.map(leaf, table, own)

    def push(self, table: Pytree, q_own: Pytree) -> Pytree:
        return jax.tree.map(jnp.add, table, self._gather(q_own))

    def push_packed(self, table: Pytree, packed, block: int) -> Pytree:
        """Fused push: `lax.all_gather` moves packed (vals, idx) records;
        the (m, nb, kpad) record table is unpacked locally per rank."""
        vals_t, idx_t = packed
        q = _unpack_like(
            self._gather(vals_t), self._gather(idx_t), table, block
        )
        return jax.tree.map(jnp.add, table, q)


def _gossiper(topo: Topology, axis: str):
    if topo.ppermute_schedule is not None:
        return _PpermuteGossiper(topo, axis)
    return _AllGatherGossiper(topo, axis)


# ---------------------------------------------------------------------------
# the device-executed C2DFB round
# ---------------------------------------------------------------------------


def _device_inner_loop(
    state: InnerState,
    key: jax.Array,
    grad_fn,
    gossip,
    compressor: Compressor,
    gamma: float,
    eta: float,
    K: int,
    rank,
    m: int,
    fused: tuple[int, int] | None = None,
):
    """Algorithm 2 on one rank (axis-1 slices): K compressed-GT steps where
    the reference mixing reads neighbor COPIES and each step's residual
    broadcast is a real collective.  Mirrors `inner_loop.inner_loop`'s scan
    body step-for-step (same key splits, same update order) — keep the two
    in lockstep.  Returns the state and the per-step payload stacks
    ``(q_d, q_s)`` (leaves (K, 1, ...)) for host-side wire metering.

    With ``fused=(block, kpad)`` each residual is packed ON-DEVICE into
    (vals, idx) records (`_pack_tree`) right after compression: the gossip
    collectives move only the records, every receiver (and the sender's
    own reference update) applies the unpacked form — bit-exact with the
    dense path for <= kpad survivors per block — and the payload stacks
    are the packed pairs, so the dense residual tree never exists on the
    host."""
    copies_d = gossip.init(state.d_hat)
    copies_s = gossip.init(state.s_hat)

    def broadcast(copies, q):
        """Pack-and-push one compressed residual; returns (copies, applied
        residual, wire payload)."""
        if fused is None:
            return gossip.push(copies, q), q, q
        block, kpad = fused
        packed = _pack_tree(q, block, kpad)
        copies = gossip.push_packed(copies, packed, block)
        q_eff = _unpack_like(*packed, q, block)
        return copies, q_eff, packed

    def body(carry, k):
        st, cd, cs = carry
        kd, ks = jax.random.split(k)

        mix_d = gossip.mix(cd, st.d_hat, rank)
        d_new = jax.tree.map(
            lambda d, md, s: d + gamma * md - eta * s, st.d, mix_d, st.s
        )
        q_d = _compress_rank(
            compressor, kd, jax.tree.map(jnp.subtract, d_new, st.d_hat),
            rank, m,
        )
        cd, q_d, pay_d = broadcast(cd, q_d)
        d_hat_new = jax.tree.map(jnp.add, st.d_hat, q_d)

        g_new = grad_fn(d_new)
        mix_s = gossip.mix(cs, st.s_hat, rank)
        s_new = jax.tree.map(
            lambda s, ms, gn, gp: s + gamma * ms + gn - gp,
            st.s, mix_s, g_new, st.g_prev,
        )
        q_s = _compress_rank(
            compressor, ks, jax.tree.map(jnp.subtract, s_new, st.s_hat),
            rank, m,
        )
        cs, q_s, pay_s = broadcast(cs, q_s)
        s_hat_new = jax.tree.map(jnp.add, st.s_hat, q_s)

        st = InnerState(
            d=d_new, d_hat=d_hat_new, s=s_new, s_hat=s_hat_new, g_prev=g_new
        )
        return (st, cd, cs), (pay_d, pay_s)

    keys = jax.random.split(key, K)
    (state, _, _), payloads = jax.lax.scan(
        body, (state, copies_d, copies_s), keys
    )
    return state, payloads


def make_device_round(
    problem: BilevelProblem,
    topo: Topology,
    cfg,
    mesh: Mesh,
    axis: str = "nodes",
    jit: bool = True,
    fused: bool = False,
):
    """Build the jitted multi-device C2DFB round: a `shard_map` over
    ``axis`` running `c2dfb.c2dfb_round_core`'s update order with every
    gossip exchange executed as a collective.  Returns
    ``fn(x, s_x, u_prev, inner_y, inner_z, key, data_f, data_g) ->
    (x, s_x, u_new, inner_y, inner_z, (q_y, q_z))`` on node-stacked trees;
    the payload stacks carry every inner message for wire metering.

    ``fused=True`` (block-sparse compressors only) fuses the Pallas pack
    kernel into the exchange: inner residuals are compressed AND packed to
    (vals, idx) records on-device, the collectives move the records, and
    the payload stacks are ``((vals, idx), ...)`` pairs with leaves
    (K, m, nb, kpad) — metered via `wire.encode_packed_records_chunked`
    without ever materializing the dense tree on the host."""
    m = topo.m
    compressor = cfg.make_compressor()
    pack_spec = fused_pack_spec(compressor) if fused else None
    gossip = _gossiper(topo, axis)

    def per_rank(x, s_x, u_prev, inner_y, inner_z, key, data_f, data_g):
        rank = jax.lax.axis_index(axis)
        lp = BilevelProblem(
            f=problem.f, g=problem.g, data_f=data_f, data_g=data_g, m=1
        )
        ky, kz = jax.random.split(key)

        # ---- outer model update (dense broadcast + tracked descent) ------
        mix_x = gossip.mix(gossip.init(x), x, rank)
        x_new = jax.tree.map(
            lambda x_, mx, s: x_ + cfg.gamma_out * mx - cfg.eta_out * s,
            x, mix_x, s_x,
        )

        # ---- inner loops on the new x ------------------------------------
        grad_h = lp.grad_y_h(cfg.lam)
        grad_g = lp.grad_y_g()
        gy = lambda d: grad_h(d, x_new)
        gz = lambda d: grad_g(d, x_new)
        inner_y = refresh_tracker(inner_y, gy)
        inner_z = refresh_tracker(inner_z, gz)
        inner_y, q_y = _device_inner_loop(
            inner_y, ky, gy, gossip, compressor, cfg.gamma_in, cfg.eta_in_y,
            cfg.K, rank, m, fused=pack_spec,
        )
        inner_z, q_z = _device_inner_loop(
            inner_z, kz, gz, gossip, compressor, cfg.gamma_in, cfg.eta_in,
            cfg.K, rank, m, fused=pack_spec,
        )

        # ---- hypergradient + tracker update ------------------------------
        u_new = lp.hyper_grad(x_new, inner_y.d, inner_z.d, cfg.lam)
        mix_s = gossip.mix(gossip.init(s_x), s_x, rank)
        s_x_new = jax.tree.map(
            lambda s, ms, un, up: s + cfg.gamma_out * ms + un - up,
            s_x, mix_s, u_new, u_prev,
        )
        return x_new, s_x_new, u_new, inner_y, inner_z, (q_y, q_z)

    spec = P(axis)
    fn = jax.shard_map(
        per_rank,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec, P(), spec, spec),
        out_specs=(spec, spec, spec, spec, spec, P(None, axis)),
        check_vma=False,
    )
    return jax.jit(fn) if jit else fn


# ---------------------------------------------------------------------------
# the transport
# ---------------------------------------------------------------------------


class DeviceTransport(Transport):
    """Executed multi-device transport over a mesh (one node per device).

    Parameters
    ----------
    mesh       : 1-D `jax.sharding.Mesh` whose axis holds one device per
                 node; None builds one from local devices at `bind`
    link       : profile name / `LinkModel` the internal fabric prices the
                 EXECUTED byte counts with ("zero" = in-process collectives
                 are not given a pretend latency; pick "wan"/"geo" to ask
                 "what would this executed traffic cost on that wire?")
    straggler  : `StragglerModel` or kind string for the pricing fabric
    verify     : check decode(encode(payload)) message-for-message
                 (bit-exact; KernelQuant to 1 ulp).  Leave on — it is the
                 deployment-correctness assertion of the backend.
    fused      : run the FUSED round (`make_device_round(fused=True)`):
                 inner residuals are compressed + packed to (vals, idx)
                 records on-device and the collectives move the records —
                 block-sparse compressors only.  Implies chunked metering
                 (``chunk`` defaults to 1 << 16).
    chunk      : when set, wire-meter every message with the CHUNKED tree
                 codec (`wire.encode_tree_chunked` — per-chunk headers, the
                 LM-scale format); executed bytes then equal
                 `wire.measure_tree_bytes_chunked` exactly.  None keeps the
                 per-leaf format of `wire.measure_tree_bytes`.
    """

    def __init__(
        self,
        mesh: Mesh | None = None,
        link="zero",
        straggler: StragglerModel | str | None = None,
        compute_s: float = 0.0,
        seed: int = 0,
        trace=None,
        axis: str = "nodes",
        verify: bool = True,
        fused: bool = False,
        chunk: int | None = None,
        **straggler_kw,
    ):
        self.mesh = mesh
        self.axis = axis if mesh is None else mesh.axis_names[0]
        self.verify = verify
        if fused and chunk is None:
            chunk = 1 << 16
        if chunk is not None and chunk <= 0:
            raise ValueError(f"chunk must be positive, got {chunk}")
        self.fused = fused
        self.chunk = chunk
        self._link = link
        if isinstance(straggler, str):
            straggler = StragglerModel(kind=straggler, **straggler_kw)
        self._straggler = straggler
        self._compute_s = compute_s
        self._seed = seed
        self._trace = trace
        self.fabric: NetworkFabric | None = None
        self._bcast = None

    # ------------------------------------------------------------------
    def bind(self, topo: Topology) -> "DeviceTransport":
        if self.fabric is not None:
            if self.fabric.topo.m != topo.m or self.fabric.topo.name != topo.name:
                raise ValueError(
                    f"DeviceTransport is bound to {self.fabric.topo.name!r} "
                    f"(m={self.fabric.topo.m}) but was asked to run on "
                    f"{topo.name!r} (m={topo.m})"
                )
            return self
        if self.mesh is None:
            self.mesh = mesh_for_nodes(topo.m, self.axis)
        mesh_m = self.mesh.shape[self.axis]
        if mesh_m != topo.m:
            raise ValueError(
                f"mesh axis {self.axis!r} has {mesh_m} devices but the "
                f"topology has {topo.m} nodes — DeviceTransport places "
                "exactly one node per device"
            )
        self.fabric = NetworkFabric(
            topo,
            link=self._link,
            straggler=self._straggler,
            compute_s=self._compute_s,
            seed=self._seed,
            trace=self._trace,
        )
        axis = self.axis
        self._bcast = jax.jit(
            jax.shard_map(
                lambda t: jax.tree.map(
                    lambda v: jax.lax.all_gather(v[0], axis), t
                ),
                mesh=self.mesh,
                in_specs=P(axis),
                out_specs=P(),
                check_vma=False,
            )
        )
        return self

    @property
    def executes(self) -> bool:
        return True

    def shard(self, tree: Pytree) -> Pytree:
        """Place a node-stacked tree with one node slice per device."""
        self._require_bound()
        return jax.device_put(tree, NamedSharding(self.mesh, P(self.axis)))

    # ------------------------------------------------------------------
    # wire round trip + verification
    # ------------------------------------------------------------------
    def _roundtrip(self, payload: Pytree, compressor: Compressor | None):
        """encode -> decode each node's message with the wire codec; verify
        the receipt against the executed payload.  Returns the decoded
        node-stacked tree (what receivers apply) and the per-node executed
        message bytes — `len(encode(...))`, i.e. `wire.measure_tree_bytes`
        of each slice by construction."""
        comp = compressor if compressor is not None else C.Identity()
        codec = codec_for(comp)
        leaves, treedef = jax.tree.flatten(payload)
        arrs = [np.asarray(leaf) for leaf in leaves]
        m = arrs[0].shape[0]
        exact = not isinstance(comp, C.KernelQuant)
        out = [np.empty_like(a, dtype=np.float32) for a in arrs]
        node_bytes = []
        for i in range(m):
            nbytes = 0
            for li, a in enumerate(arrs):
                wire = codec.encode(a[i].reshape(-1))
                nbytes += len(wire)
                dec = codec.decode(wire).reshape(a[i].shape)
                if self.verify:
                    sent = a[i].astype(np.float32)
                    if exact:
                        if not np.array_equal(dec, sent):
                            raise AssertionError(
                                f"wire codec round-trip mismatch on node {i}"
                                f", leaf {li}: the executed payload did not "
                                "survive encode->decode bit-exactly"
                            )
                    elif not np.allclose(dec, sent, rtol=1e-5, atol=0):
                        raise AssertionError(
                            f"KernelQuant wire round-trip drifted past 1-ulp"
                            f" tolerance on node {i}, leaf {li}"
                        )
                out[li][i] = dec
            node_bytes.append(nbytes)
        decoded = [
            jnp.asarray(o).astype(leaf.dtype) for o, leaf in zip(out, leaves)
        ]
        return jax.tree.unflatten(treedef, decoded), tuple(node_bytes)

    def exchange(
        self,
        payload: Pytree,
        compressor: Compressor | None = None,
        round_idx: int = 0,
        phase_idx: int = 0,
        label: str = "exchange",
        edges=None,
    ) -> tuple[Pytree, ExchangeReport]:
        self._require_bound()
        edges = self._edge_set(edges)
        t0 = time.perf_counter()
        decoded, node_bytes = self._roundtrip(payload, compressor)
        edge_bytes = {(i, j): node_bytes[i] for (i, j) in edges}
        wire_bytes = int(sum(edge_bytes.values()))
        delivered = self._bcast(self.shard(decoded))
        jax.block_until_ready(jax.tree.leaves(delivered))
        wall = time.perf_counter() - t0
        duration = self._price_phase(edge_bytes, round_idx, phase_idx)
        return delivered, ExchangeReport(
            node_bytes=node_bytes,
            wire_bytes=wire_bytes,
            duration_s=duration,
            wall_s=wall,
            label=label,
        )

    def _roundtrip_chunked(
        self, payload: Pytree, compressor: Compressor | None
    ) -> tuple:
        """Chunked twin of `_roundtrip`: encode -> decode each node's
        message with `wire.encode_tree_chunked` (per-chunk headers, the
        LM-scale format) and verify the decoded stream bit-exactly.
        Returns per-node executed bytes (== `measure_tree_bytes_chunked`
        of each slice by construction)."""
        comp = compressor if compressor is not None else C.Identity()
        codec = codec_for(comp)
        node_bytes = []
        leaves = [np.asarray(l) for l in jax.tree.leaves(payload)]
        m = leaves[0].shape[0]
        for i in range(m):
            slc = [a[i] for a in leaves]
            payloads = codec.encode_tree_chunked(slc, self.chunk)
            node_bytes.append(sum(len(p) for p in payloads))
            if self.verify:
                dec = codec.decode_tree_chunked(payloads, slc)
                sent = np.concatenate(
                    [np.asarray(a, np.float32).reshape(-1) for a in slc]
                )
                got = np.concatenate(
                    [np.asarray(a).reshape(-1) for a in dec]
                )
                if not np.array_equal(got, sent):
                    raise AssertionError(
                        f"chunked wire round-trip mismatch on node {i}: "
                        "the executed payload did not survive "
                        "encode->decode bit-exactly"
                    )
        return tuple(node_bytes)

    def _packed_node_bytes(
        self, vals_leaves, idx_leaves, k, leaf_sizes, block: int
    ) -> tuple:
        """Executed bytes of inner step ``k``'s per-node messages built
        DIRECTLY from the on-device packed records — the fused path's
        codec truth (byte-identical to chunked-encoding the dense tree,
        which never exists on the host here)."""
        chunk = self.chunk if self.chunk is not None else 1 << 16
        m = vals_leaves[0].shape[1]
        node_bytes = []
        for i in range(m):
            vlist = [v[k, i] for v in vals_leaves]
            ilist = [ix[k, i] for ix in idx_leaves]
            payloads = wire.encode_packed_records_chunked(
                vlist, ilist, leaf_sizes, block, chunk
            )
            node_bytes.append(sum(len(p) for p in payloads))
            if self.verify:
                dec = np.concatenate(
                    [wire.SparseCodec().decode(p) for p in payloads]
                )
                ref = wire.scatter_packed_records(
                    vlist, ilist, leaf_sizes, block
                )
                if not np.array_equal(dec, ref):
                    raise AssertionError(
                        f"packed-record wire round-trip mismatch on node "
                        f"{i}, inner step {k}: decoded chunks disagree "
                        "with the scattered records"
                    )
        return tuple(node_bytes)

    # ------------------------------------------------------------------
    def meter_round(
        self,
        outer_payloads,
        inner_stacks,
        compressor: Compressor,
        round_idx: int,
        packed: bool = False,
        inner_like: Pytree | None = None,
    ) -> dict:
        """Wire-account one executed round: run every message of the round
        through the codec round trip (verification included) and price the
        resulting EXECUTED byte counts on the internal fabric, advancing
        its clock — the device twin of pricing `c2dfb.round_phases`.

        ``outer_payloads``: [(label, dense node-stacked tree), ...];
        ``inner_stacks``: [(tag, (q_d, q_s) with (K, m, ...) leaves), ...].
        Returns {"sim_seconds", "wire_bytes", "node_bytes"} where
        ``node_bytes`` maps phase label -> per-node executed message bytes
        (== `wire.measure_tree_bytes` per node slice — or its chunked twin
        when ``self.chunk`` is set — tested).

        ``packed=True`` (the fused round): inner stacks are the on-device
        packed ``((vals, idx), ...)`` record pairs with leaves
        (K, m, nb, kpad); bytes come from
        `wire.encode_packed_records_chunked` against ``inner_like`` (one
        node's residual tree template supplying leaf sizes), byte-identical
        to chunk-encoding the dense tree the records represent.

        Accounting note vs the sim backend: every byte here is codec
        truth, INCLUDING the dense outer broadcasts (DenseCodec pays a
        5-byte header per leaf), whereas `c2dfb.round_phases` prices the
        outer phases headerless (``d * 4``, the paper's accounting) and
        inner phases at steady-state sizes — so the two backends' priced
        ``wire_bytes``/``sim_seconds`` agree closely but not to the
        byte."""
        self._require_bound()
        edges = self._edge_set(None)
        phases, labels, per_phase_nb = [], [], {}

        def add_phase(label, nb):
            phases.append({(i, j): nb[i] for (i, j) in edges})
            labels.append(label)
            per_phase_nb[label] = nb

        def dense_nb(tree, comp):
            if self.chunk is None:
                _, nb = self._roundtrip(tree, comp)
                return nb
            return self._roundtrip_chunked(tree, comp)

        for label, tree in outer_payloads:
            add_phase(label, dense_nb(tree, None))
        if packed:
            if inner_like is None:
                raise ValueError(
                    "packed metering needs inner_like (one node's residual "
                    "tree template) to recover leaf sizes"
                )
            block, _ = fused_pack_spec(compressor)
            leaf_sizes = [
                int(np.prod(np.shape(l)))
                for l in jax.tree.leaves(inner_like)
            ]
            for tag, stacks in inner_stacks:
                rec = {
                    name: (
                        [np.asarray(v) for v in jax.tree.leaves(vals_t)],
                        [np.asarray(v) for v in jax.tree.leaves(idx_t)],
                    )
                    for name, (vals_t, idx_t) in (
                        ("d", stacks[0]), ("s", stacks[1])
                    )
                }
                K = rec["d"][0][0].shape[0]
                for k in range(K):
                    for name in ("d", "s"):
                        vals_leaves, idx_leaves = rec[name]
                        add_phase(
                            f"{tag}/in{k}/{name}",
                            self._packed_node_bytes(
                                vals_leaves, idx_leaves, k, leaf_sizes,
                                block,
                            ),
                        )
        else:
            for tag, (q_d, q_s) in inner_stacks:
                K = jax.tree.leaves(q_d)[0].shape[0]
                for k in range(K):
                    for name, stack in (("d", q_d), ("s", q_s)):
                        step_tree = jax.tree.map(lambda v, k=k: v[k], stack)
                        add_phase(
                            f"{tag}/in{k}/{name}",
                            dense_nb(step_tree, compressor),
                        )
        rep = self.fabric.simulate_round(phases, round_idx, labels=labels)
        return {
            "sim_seconds": rep["sim_seconds"],
            "wire_bytes": rep["wire_bytes"],
            "node_bytes": per_phase_nb,
        }
