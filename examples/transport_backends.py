"""One algorithm, two transports: priced simulation vs executed devices.

    PYTHONPATH=src python examples/transport_backends.py

The same C2DFB run goes through both `repro.transport` backends.
`SimTransport` wraps the network fabric — the familiar priced-simulation
path, bit-exact with passing the fabric directly.  `DeviceTransport` puts
one bilevel node on each of 8 virtual CPU devices (set up by the XLA flag
below) and EXECUTES every gossip exchange: `lax.ppermute` collectives
carry the compressed residuals between ranks, and every message makes the
wire-codec encode -> decode round trip, so the byte counts are produced by
running serialization code, not by an estimator.  A future multi-process
backend (jax.distributed send/recv, UCX) slots into the same protocol.
"""

import os

# one device per node — must be set before jax is imported (append so a
# pre-existing XLA_FLAGS export keeps its other flags)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.c2dfb import C2DFBConfig, run  # noqa: E402
from repro.core.topology import ring  # noqa: E402
from repro.data.bilevel_tasks import coefficient_tuning_task  # noqa: E402
from repro.net import make_fabric  # noqa: E402
from repro.transport import DeviceTransport, SimTransport  # noqa: E402


def main():
    m, T = 8, 6
    bundle = coefficient_tuning_task(m=m, n=800, p=60, c=5, h=0.8, seed=0)
    topo = ring(m)
    cfg = C2DFBConfig(
        lam=10.0, eta_out=0.2, gamma_out=0.5, eta_in=0.2, gamma_in=0.4,
        K=6, compressor="topk", comp_ratio=0.3,
    )
    key = jax.random.PRNGKey(0)

    backends = {
        "sim   ": SimTransport(make_fabric(topo, profile="wan", seed=0)),
        "device": DeviceTransport(link="wan", seed=0),
    }
    print(f"{m} nodes on a ring, {T} rounds, topk-compressed inner loops\n")
    for name, transport in backends.items():
        state, mets = run(
            bundle.problem, topo, cfg, bundle.x0, bundle.y0, T=T, key=key,
            transport=transport,
        )
        err = float(np.asarray(mets["y_consensus_err"])[-1])
        print(
            f"[{name}] consensus_err={err:.3e}  "
            f"wire_MB={np.asarray(mets['wire_bytes']).sum() / 1e6:.2f}  "
            f"sim_s={np.asarray(mets['sim_seconds']).sum():.1f}"
            + (
                f"  wall_s={np.asarray(mets['wall_seconds']).sum():.1f}"
                if "wall_seconds" in mets
                else ""
            )
        )
    print(
        "\nSame math, same wire format — the device row was executed as "
        "shard_map collectives\nwith codec-serialized payloads; the sim row "
        "was priced on the link model."
    )


if __name__ == "__main__":
    main()
