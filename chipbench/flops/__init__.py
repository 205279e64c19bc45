"""Required FLOPs of one C2DFB round, one module per architecture kind
(``arch_type`` of the configuration), each with ``backbone_forward(mc,
tokens)`` and ``head_forward(mc, tokens)`` for one node's batch.

The round's required work per node is 8 F_bb + (6K + 12) F_head
(see `round_flops`); recomputation by the program is not counted.
"""

from __future__ import annotations

import importlib


def round_flops(mc: dict, traffic: dict) -> float:
    """FLOPs Algorithm 1's oracle calls require in one round, all nodes.

    Per node: the backbone forward at x_{t+1} once per data set (x is
    fixed through the inner loops): 2 F_bb; each of the (K+1) grad_y h
    calls a head forward and weight gradient on both sets: 4 F_head;
    each of the (K+1) grad_y g calls: 2 F_head; each of the three
    x-partials a head forward and input gradient (2 F_head) and the
    backbone's backward (2 F_bb; its forward is the one counted above):
    3 (2 F_head + 2 F_bb).  Sum: 8 F_bb + (6K + 12) F_head.
    """
    arch = importlib.import_module(f"chipbench.flops.{mc['arch_type']}")
    tokens = traffic["batch"] * traffic["seq"]
    f_bb = arch.backbone_forward(mc, traffic["seq"], tokens)
    f_head = head_forward(mc, tokens)
    K = traffic["K"]
    return traffic["nodes"] * (8 * f_bb + (6 * K + 12) * f_head)


def head_forward(mc: dict, tokens: int) -> float:
    return 2.0 * mc["d_model"] * mc["vocab_size"] * tokens
