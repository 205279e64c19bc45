"""Roofline-term extraction from compiled dry-run artifacts.

compute    = HLO_FLOPs        / peak_flops_bf16
memory     = HLO_bytes        / hbm_bytes_per_s
collective = collective_bytes / (ici_bytes_per_s_per_link * links)

with the peaks of the named device kind (`PEAKS`; an unknown kind is an
error, never a default).

collective_bytes comes from parsing the (post-SPMD-partitioning) HLO text:
we sum OPERAND sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute op.  Shapes in the compiled module are
already per-device, so the sum is per-chip wire bytes per step (one ring
pass lower-bound; schedules that send more are noted in EXPERIMENTS.md).
"""

from __future__ import annotations

import re
from collections import defaultdict

#: published per-chip peaks, keyed by ``jax.Device.device_kind``.
#: "TPU v5 lite" (v5e): Google Cloud documentation, "TPU v5e" — 197
#: TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s interchip interconnect
#: (4 links x 50 GB/s).
PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s_per_link": 50e9,
        "ici_links": 4,
    },
}


def peaks(device_kind: str) -> dict:
    """The `PEAKS` row of ``device_kind``; raises for a kind not listed."""
    if device_kind not in PEAKS:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known kinds: {sorted(PEAKS)}"
        )
    return PEAKS[device_kind]

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """'bf16[8,128]' -> 2048.  Tuple shapes handled by summing members."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-shape bytes per collective kind from HLO text."""
    out: dict = defaultdict(int)
    out_counts: dict = defaultdict(int)
    for line in hlo_text.splitlines():
        s = line.strip()
        # match:  %name = TYPE[s](...) all-reduce(...)  / all-reduce-start etc.
        mm = re.search(r"=\s*(\S+)\s+(\S+)\(", s)
        if not mm:
            continue
        op = mm.group(2)
        base = None
        for c in _COLLECTIVES:
            if op == c or op.startswith(c + "-start") or op == c + ".clone":
                base = c
                break
        if base is None:
            continue
        nbytes = _shape_bytes(mm.group(1))
        out[base] += nbytes
        out_counts[base] += 1
    return {
        "bytes_by_kind": dict(out),
        "counts_by_kind": dict(out_counts),
        "total_bytes": int(sum(out.values())),
    }


def roofline_terms(
    flops: float, hbm_bytes: float, coll_bytes: float, chips: int,
    device_kind: str,
) -> dict:
    """All terms in seconds on ``device_kind``'s peaks.  flops/hbm_bytes
    are WHOLE-PROGRAM numbers from cost_analysis (already per-device after
    SPMD partitioning)."""
    pk = peaks(device_kind)
    compute_s = flops / pk["flops_bf16"]
    memory_s = hbm_bytes / pk["hbm_bytes_per_s"]
    collective_s = coll_bytes / (
        pk["ici_bytes_per_s_per_link"] * pk["ici_links"]
    )
    dominant = max(
        ("compute", compute_s), ("memory", memory_s), ("collective", collective_s),
        key=lambda kv: kv[1],
    )[0]
    return {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "dominant": dominant,
        "chips": chips,
        "device_kind": device_kind,
    }


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D tokens (dense) / 6*N_active*D (MoE), per step."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens  # forward only
    tokens = shape.global_batch  # decode: one token per sequence
    return 2.0 * n * tokens
