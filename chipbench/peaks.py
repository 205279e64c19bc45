"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

"TPU v5 lite" (v5e): Google Cloud documentation, "TPU v5e" -- 197 TFLOP/s
bf16, 819 GB/s HBM, 16 GB HBM.  An unknown kind is an error, never a
default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known kinds: {sorted(PEAKS)}"
        )
    return PEAKS[device_kind]
