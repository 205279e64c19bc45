"""Production mesh construction.

Functions only — importing this module never touches jax device state.
Target hardware: TPU v5e, 16x16 = 256 chips per pod; 2 pods = 512 chips
(peak rates per device kind: `repro.launch.roofline.PEAKS`).
"""

from __future__ import annotations

import jax


def _auto(n):
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"), axis_types=_auto(2))

