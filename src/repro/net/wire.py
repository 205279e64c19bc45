"""Exact wire-format codecs for compressed residuals.

``Compressor.leaf_wire_bytes`` is an analytic float estimate; this module is
the real thing: each codec serializes a compressor's *output* tensor to the
byte string a deployment would put on the wire, and deserializes it back.
``measure`` therefore returns integer bytes including headers, and
``decode(encode(q)) == q`` bitwise for every compressor except KernelQuant,
whose XLA-fused dequant epilogue can differ from the canonical receiver by
1 ulp — there the wire representation itself (codes + scales) round-trips
losslessly (see ``_dequant``; both contracts are tested).

Formats (little-endian):

* sparse   ``b"S" | u32 d | u32 nnz | nnz*u32 idx | nnz*f32 vals``
  for magnitude/coordinate sparsifiers (TopK, RandK, BlockTopK,
  KernelBlockTopK).  Block variants pack via the Pallas kernel
  (`repro.kernels.pack_residuals`) and globalize the per-block lane ids.
* quant    ``b"Q" | u32 d | u8 bits | u32 block | nb*f32 scales | codes``
  for stochastic quantizers; codes are bit-packed to ``bits`` each.  Scales
  are recovered from the dequantized output (the argmax input element maps
  exactly to +/-scale), so the codec needs no side channel.
* dense    ``b"D" | u32 d | d*f32``
  for Identity / LowRank fallbacks.

Codecs run host-side on numpy; they meter and check the SPMD simulator, they
are not inside jit.
"""

from __future__ import annotations

import dataclasses
import struct

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compression as C
from repro.core.types import Pytree
from repro.obs.scopes import scope

_HDR_S = struct.Struct("<cII")    # kind, d, nnz
_HDR_Q = struct.Struct("<cIBI")   # kind, d, bits, block
_HDR_D = struct.Struct("<cI")     # kind, d


def _flatten_f32(tree: Pytree) -> np.ndarray:
    """All leaves as one contiguous f32 stream (leaf order = jax.tree)."""
    leaves = [np.asarray(l, np.float32).reshape(-1) for l in jax.tree.leaves(tree)]
    return np.concatenate(leaves) if leaves else np.zeros(0, np.float32)


class WireCodec:
    """Serialize one compressed leaf (flattened) to wire bytes and back."""

    def encode(self, q: np.ndarray) -> bytes:
        raise NotImplementedError

    def decode(self, payload: bytes) -> np.ndarray:
        raise NotImplementedError

    def measure(self, q: np.ndarray) -> int:
        return len(self.encode(q))

    # -- pytree conveniences ------------------------------------------------
    def encode_tree(self, tree: Pytree) -> list[bytes]:
        return [
            self.encode(np.asarray(leaf).reshape(-1))
            for leaf in jax.tree.leaves(tree)
        ]

    def tree_bytes(self, tree: Pytree) -> int:
        return sum(len(p) for p in self.encode_tree(tree))

    # -- chunked pytree path (LM-scale trees) -------------------------------
    def _check_chunkable(self, chunk: int) -> None:
        if chunk <= 0:
            raise ValueError(f"chunk must be positive, got {chunk}")
        if isinstance(self, QuantCodec):
            # quant scales are recovered from per-tile maxima; re-tiling a
            # concatenated stream changes the tiles, so chunked quant would
            # not round-trip — the LM residual path is sparse (top-k)
            raise ValueError(
                "chunked encoding is defined for sparse/dense codecs; "
                "QuantCodec tiles are position-dependent and would not "
                "survive re-chunking"
            )

    def encode_tree_chunked(self, tree: Pytree, chunk: int = 1 << 16) -> list[bytes]:
        """One payload per CHUNK instead of per leaf: all leaves are
        flattened (f32) into a single stream and split into ``chunk``-
        element segments, each encoded independently.  For transformer-
        sized pytrees (hundreds of small-and-large leaves) this amortizes
        per-leaf headers into per-chunk headers and bounds every index
        payload to ``chunk`` — the wire format for LM-scale fabric runs.
        Exact-parity decode vs the per-leaf path is tested in
        tests/test_net_wire.py."""
        self._check_chunkable(chunk)
        flat = _flatten_f32(tree)
        return [
            self.encode(flat[off : off + chunk])
            for off in range(0, flat.size, chunk)
        ]

    def decode_tree_chunked(self, payloads: list, tree_like: Pytree) -> Pytree:
        """Inverse of `encode_tree_chunked`; ``tree_like`` supplies the
        leaf shapes/structure (its values are ignored)."""
        flat = np.concatenate([self.decode(p) for p in payloads]) if payloads \
            else np.zeros(0, np.float32)
        leaves, treedef = jax.tree.flatten(tree_like)
        total = sum(int(np.size(l)) for l in leaves)
        if flat.size != total:
            raise ValueError(
                f"chunked payloads decode to {flat.size} elements but the "
                f"tree has {total}"
            )
        out, off = [], 0
        for leaf in leaves:
            n = int(np.size(leaf))
            out.append(flat[off : off + n].reshape(np.shape(leaf)))
            off += n
        return jax.tree.unflatten(treedef, out)

    def tree_bytes_chunked(self, tree: Pytree, chunk: int = 1 << 16) -> int:
        return sum(len(p) for p in self.encode_tree_chunked(tree, chunk))


@dataclasses.dataclass(frozen=True)
class DenseCodec(WireCodec):
    def encode(self, q: np.ndarray) -> bytes:
        q = np.asarray(q, np.float32).reshape(-1)
        return _HDR_D.pack(b"D", q.size) + q.tobytes()

    def decode(self, payload: bytes) -> np.ndarray:
        kind, d = _HDR_D.unpack_from(payload)
        assert kind == b"D", kind
        return np.frombuffer(payload, np.float32, count=d, offset=_HDR_D.size)


@dataclasses.dataclass(frozen=True)
class SparseCodec(WireCodec):
    """(u32 index, f32 value) records for any zero-masked sparsifier."""

    def encode(self, q: np.ndarray) -> bytes:
        q = np.asarray(q, np.float32).reshape(-1)
        idx = np.flatnonzero(q).astype(np.uint32)
        vals = q[idx]
        return (
            _HDR_S.pack(b"S", q.size, idx.size)
            + idx.tobytes()
            + vals.tobytes()
        )

    def decode(self, payload: bytes) -> np.ndarray:
        kind, d, nnz = _HDR_S.unpack_from(payload)
        assert kind == b"S", kind
        off = _HDR_S.size
        idx = np.frombuffer(payload, np.uint32, count=nnz, offset=off)
        vals = np.frombuffer(
            payload, np.float32, count=nnz, offset=off + 4 * nnz
        )
        out = np.zeros(d, np.float32)
        out[idx] = vals
        return out


@dataclasses.dataclass(frozen=True)
class BlockSparseCodec(SparseCodec):
    """SparseCodec whose record extraction runs through the Pallas
    pack kernel — the deployment path for BlockTopK residuals.  The wire
    format is identical to SparseCodec (global u32 indices), so the two
    decode interchangeably; only the packing engine differs."""

    block: int = 1024
    ratio: float = 0.2

    def encode(self, q: np.ndarray) -> bytes:
        from repro.kernels.pack_residuals import pack_sparse_blocks

        q = np.asarray(q, np.float32).reshape(-1)
        d = q.size
        nb = -(-d // self.block)
        padded = np.zeros(nb * self.block, np.float32)
        padded[:d] = q
        # budget = the worst row's actual survivor count, so the pack can
        # never drop a record even when the bisection kernel keeps more
        # than the nominal ratio*block per block
        nnz_max = int(
            np.count_nonzero(padded.reshape(nb, self.block), axis=1).max()
        )
        k = min(self.block, max(1, nnz_max))
        vals, idx = pack_sparse_blocks(
            jnp.asarray(padded.reshape(nb, self.block)), k=k, block=self.block
        )
        vals, idx = np.asarray(vals), np.asarray(idx)
        valid = idx < self.block
        gidx = (
            idx + self.block * np.arange(nb, dtype=np.int32)[:, None]
        )[valid].astype(np.uint32)
        gvals = vals[valid]
        order = np.argsort(gidx, kind="stable")
        return (
            _HDR_S.pack(b"S", d, gidx.size)
            + gidx[order].tobytes()
            + gvals[order].tobytes()
        )


@dataclasses.dataclass(frozen=True)
class QuantCodec(WireCodec):
    """Bit-packed stochastic-quantization codes + per-block f32 scales.

    The compressor hands us the *dequantized* tensor; codes and scales are
    recovered exactly because the per-block argmax element always lands on
    the +/-scale grid point (valid whenever max|x| exceeded the 1e-12
    clamp).  Decode replays the canonical dequant arithmetic (``_dequant``),
    value-bit-exact for ``StochasticQuant`` and 1-ulp for ``KernelQuant``.
    """

    bits: int = 4
    block: int = 0  # 0 = one scale for the whole leaf (StochasticQuant)

    def _blocks(self, d: int) -> int:
        return 1 if self.block == 0 else -(-d // self.block)

    def encode(self, q: np.ndarray) -> bytes:
        q = np.asarray(q, np.float32).reshape(-1)
        d = q.size
        blk = d if self.block == 0 else self.block
        nb = self._blocks(d)
        padded = np.zeros(nb * blk, np.float32)
        padded[:d] = q
        tiles = padded.reshape(nb, blk)
        scales = np.maximum(np.abs(tiles).max(axis=1), 1e-12).astype(np.float32)
        levels = np.float32((1 << self.bits) - 1)
        y = tiles / scales[:, None]
        codes = np.rint((y + np.float32(1.0)) * np.float32(0.5) * levels)
        codes = np.clip(codes, 0, int(levels)).astype(np.uint8).reshape(-1)[: d]
        packed = _pack_bits(codes, self.bits)
        return (
            _HDR_Q.pack(b"Q", d, self.bits, self.block)
            + scales.tobytes()
            + packed.tobytes()
        )

    def decode(self, payload: bytes) -> np.ndarray:
        kind, d, bits, block = _HDR_Q.unpack_from(payload)
        assert kind == b"Q", kind
        blk = d if block == 0 else block
        nb = 1 if block == 0 else -(-d // block)
        off = _HDR_Q.size
        scales = np.frombuffer(payload, np.float32, count=nb, offset=off)
        codes = _unpack_bits(
            np.frombuffer(payload, np.uint8, offset=off + 4 * nb), bits, d
        )
        padded = np.zeros(nb * blk, np.float32)
        padded[:d] = codes
        out = _dequant(padded.reshape(nb, blk), scales, bits)
        return out.reshape(-1)[:d].astype(np.float32)


def _dequant(codes: np.ndarray, scales: np.ndarray, bits: int) -> np.ndarray:
    """Canonical receiver-side dequant: IEEE op-by-op float32, identical to
    the eager jnp arithmetic in ``StochasticQuant`` (value-bit-exact round
    trip).  The Pallas ``KernelQuant`` runs the same chain *fused* under
    XLA, which may round the epilogue differently by <= 1 ulp — for that
    compressor the wire is information-exact (codes and scales are carried
    losslessly) while decoded values can differ in the last bit; tests pin
    both contracts."""
    levels = np.float32((1 << bits) - 1)
    deq = codes.astype(np.float32) / levels * np.float32(2.0) - np.float32(1.0)
    return deq * scales[:, None].astype(np.float32)


def _pack_bits(codes: np.ndarray, bits: int) -> np.ndarray:
    """Pack b-bit codes (uint8, values < 2^bits) into a dense byte stream."""
    cbits = np.unpackbits(codes[:, None], axis=1, count=8)[:, 8 - bits :]
    return np.packbits(cbits.reshape(-1))


def _unpack_bits(packed: np.ndarray, bits: int, n: int) -> np.ndarray:
    cbits = np.unpackbits(packed)[: n * bits].reshape(n, bits)
    pad = np.zeros((n, 8 - bits), np.uint8)
    return np.packbits(np.concatenate([pad, cbits], axis=1), axis=1).reshape(-1)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


def has_exact_codec(compressor: C.Compressor) -> bool:
    """True when ``codec_for`` implements this compressor's actual wire
    format.  LowRank (and any future compressor without a codec) falls back
    to DenseCodec, which serializes the reconstruction — a valid wire but
    NOT what a deployment would send (the rank-r factors), so byte
    measurements there must not be compared against ``leaf_wire_bytes``."""
    if isinstance(compressor, C.Rescaled):
        return has_exact_codec(compressor.inner)
    return isinstance(
        compressor,
        (
            C.Identity,
            C.TopK,
            C.RandK,
            C.BlockTopK,
            C.KernelBlockTopK,
            C.StochasticQuant,
            C.KernelQuant,
        ),
    )


def codec_for(compressor: C.Compressor) -> WireCodec:
    """The wire codec a deployment would pair with this compressor.
    Compressors without a dedicated format fall back to DenseCodec — check
    ``has_exact_codec`` before treating the measurement as deployment
    truth."""
    if isinstance(compressor, (C.BlockTopK, C.KernelBlockTopK)):
        return BlockSparseCodec(
            block=compressor.block, ratio=compressor.ratio
        )
    if isinstance(compressor, (C.TopK, C.RandK)):
        return SparseCodec()
    if isinstance(compressor, C.StochasticQuant):
        return QuantCodec(bits=compressor.bits, block=0)
    if isinstance(compressor, C.KernelQuant):
        return QuantCodec(bits=compressor.bits, block=compressor.block)
    if isinstance(compressor, C.Rescaled):
        return codec_for(compressor.inner)
    return DenseCodec()


def measure_tree_bytes(compressor: C.Compressor, tree: Pytree) -> int:
    """Exact integer wire bytes for one transmission of ``tree`` (already
    compressed).  Replaces ``Compressor.tree_wire_bytes`` estimates."""
    return codec_for(compressor).tree_bytes(tree)


def measure_compressed_tree_bytes(
    compressor: C.Compressor, key, tree: Pytree
) -> int:
    """Compress ``tree`` with ``compressor`` then measure the wire bytes."""
    return measure_tree_bytes(compressor, compressor.compress_tree(key, tree))


def measure_tree_bytes_chunked(
    compressor: C.Compressor, tree: Pytree, chunk: int = 1 << 16
) -> int:
    """Exact integer wire bytes of one chunked transmission (per-chunk
    headers instead of per-leaf — see `WireCodec.encode_tree_chunked`)."""
    return codec_for(compressor).tree_bytes_chunked(tree, chunk)


# ---------------------------------------------------------------------------
# packed-record fast path (fused on-device compression, no dense tree)
# ---------------------------------------------------------------------------


def encode_packed_records_chunked(
    vals_list: list[np.ndarray],
    idx_list: list[np.ndarray],
    leaf_sizes: list[int],
    block: int,
    chunk: int = 1 << 16,
) -> list[bytes]:
    """Chunked sparse wire payloads built DIRECTLY from the Pallas pack
    kernel's ``(vals, idx)`` records — the fused `DeviceTransport` path,
    where the dense residual tree never exists on the host.

    ``vals_list`` / ``idx_list`` hold one ``(nb, kpad)`` record pair per
    leaf (f32 values, i32 per-block lane ids, sentinel ``idx == block``
    past a block's nnz); ``leaf_sizes`` are the UNPADDED flat sizes in
    `jax.tree` leaf order.  Per-block lane ids are globalized into the
    flattened-tree f32 stream, sorted ascending, and split at ``chunk``
    boundaries into exactly the payloads
    ``BlockSparseCodec.encode_tree_chunked`` would emit over the dense
    tree — BYTE-IDENTICAL (both are the ascending nonzero records of each
    chunk under the same ``_HDR_S`` header; pinned in
    tests/test_lm_transport.py), so executed fused bytes still equal
    `measure_tree_bytes_chunked` exactly."""
    if chunk <= 0:
        raise ValueError(f"chunk must be positive, got {chunk}")
    if not (len(vals_list) == len(idx_list) == len(leaf_sizes)):
        raise ValueError("vals/idx/leaf_sizes must align leaf-for-leaf")
    gidx_all, vals_all = [], []
    off = 0
    for vals, idx, d in zip(vals_list, idx_list, leaf_sizes):
        vals = np.asarray(vals, np.float32)
        idx = np.asarray(idx)
        nb = vals.shape[0]
        valid = idx < block
        g = (idx + block * np.arange(nb, dtype=np.int64)[:, None])[valid]
        v = vals[valid]
        # drop tile padding past the leaf's true size (defensive: the pad
        # region is zeros, so the pack kernel never emits records there)
        keep = g < d
        gidx_all.append(off + g[keep])
        vals_all.append(v[keep])
        off += int(d)
    total = off
    gidx = (
        np.concatenate(gidx_all) if gidx_all else np.zeros(0, np.int64)
    )
    vals = (
        np.concatenate(vals_all) if vals_all else np.zeros(0, np.float32)
    )
    order = np.argsort(gidx, kind="stable")
    gidx, vals = gidx[order], vals[order]
    payloads = []
    for coff in range(0, total, chunk):
        dc = min(chunk, total - coff)
        lo = int(np.searchsorted(gidx, coff, "left"))
        hi = int(np.searchsorted(gidx, coff + dc, "left"))
        local = (gidx[lo:hi] - coff).astype(np.uint32)
        payloads.append(
            _HDR_S.pack(b"S", dc, hi - lo)
            + local.tobytes()
            + vals[lo:hi].astype(np.float32).tobytes()
        )
    return payloads


def scatter_packed_records(
    vals_list: list[np.ndarray],
    idx_list: list[np.ndarray],
    leaf_sizes: list[int],
    block: int,
) -> np.ndarray:
    """Host oracle for the packed form: scatter ``(vals, idx)`` records to
    the flattened-tree f32 stream (what a receiver reconstructs) — the
    verification reference `DeviceTransport` checks decoded chunks
    against in fused mode."""
    out = np.zeros(int(sum(leaf_sizes)), np.float32)
    off = 0
    for vals, idx, d in zip(vals_list, idx_list, leaf_sizes):
        vals = np.asarray(vals, np.float32)
        idx = np.asarray(idx)
        nb = vals.shape[0]
        valid = idx < block
        g = (idx + block * np.arange(nb, dtype=np.int64)[:, None])[valid]
        v = vals[valid]
        keep = g < d
        out[off + g[keep]] = v[keep]
        off += int(d)
    return out


# ---------------------------------------------------------------------------
# jit-compatible byte counting (exact per-message bytes inside lax.scan)
# ---------------------------------------------------------------------------


def _is_sparse_format(compressor: C.Compressor) -> bool:
    return isinstance(
        compressor, (C.TopK, C.RandK, C.BlockTopK, C.KernelBlockTopK)
    )


def scan_tree_bytes(compressor: C.Compressor, tree: Pytree) -> jax.Array:
    """Exact wire bytes of one node-stacked transmission, computed with jnp
    ops so it can run INSIDE jit/lax.scan.

    ``tree`` is the compressed payload (leading node axis m on every leaf);
    the count is per-node *broadcast* accounting — each node's message
    counted once — summed over nodes, matching
    ``codec_for(compressor).tree_bytes`` applied per node slice (tested in
    tests/test_async_gossip.py).  Sparse formats count the actual nonzeros
    of the payload (an nnz counter, not the analytic k*d estimate); quant
    and dense formats are shape-static.

    Accumulates in int64 so multi-gigabyte rounds stay exact; with x64
    disabled (the repo's test default) JAX lowers this to int32, which is
    exact up to 2 GiB per transmission x K steps — enable
    ``jax_enable_x64`` for LM-scale byte metering.
    """
    if isinstance(compressor, C.Rescaled):
        return scan_tree_bytes(compressor.inner, tree)
    acc_dtype = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    with scope("meter"):
        total = jnp.asarray(0, acc_dtype)
        for leaf in jax.tree.leaves(tree):
            m = int(leaf.shape[0])
            d = int(leaf.size // m)
            if _is_sparse_format(compressor):
                nnz = jnp.count_nonzero(leaf).astype(jnp.int32)
                total = total + m * _HDR_S.size + 8 * nnz
            elif isinstance(compressor, C.StochasticQuant):
                total = total + m * (
                    _HDR_Q.size + 4 + -(-d * compressor.bits // 8)
                )
            elif isinstance(compressor, C.KernelQuant):
                nb = -(-d // compressor.block)
                total = total + m * (
                    _HDR_Q.size + 4 * nb + -(-d * compressor.bits // 8)
                )
            else:  # Identity / LowRank fallback: dense f32 reconstruction
                total = total + m * (_HDR_D.size + 4 * d)
        return total
