"""Pallas block top-k (`repro.kernels.topk_compress`).  Each call reads
the residual and writes the sparse output, both in the state's dtype,
over (rows padded to 8) x block tiles.  No MXU work beyond a 256-lane
prefix count: bytes bound it."""

PATTERN = r"^block_topk_pallas(\.\d+)?$"
BLOCK_ROWS = 8


def padded_elements(size: int, block: int) -> int:
    nb = -(-size // block)
    return (-(-nb // BLOCK_ROWS) * BLOCK_ROWS) * block


def round_bytes(leaf_sizes, itemsize: int, traffic: dict) -> float:
    """``leaf_sizes``: element counts of the lower variable's leaves, per
    node.  2 loops x K steps x 2 messages, each compressing every leaf on
    every node."""
    calls = 2 * traffic["K"] * 2 * traffic["nodes"]
    per = sum(2 * padded_elements(s, traffic["comp_block"]) * itemsize
              for s in leaf_sizes)
    return float(calls * per)
