"""Required FLOPs of one C2DFB round: the backbone's from the kind module
of each period position (`chipbench.kinds`, ``forward_flops``), the
head's here, each for one node's batch.

The round's required work per node is 8 F_bb + (6K + 12) F_head
(see `round_flops`); recomputation by the program is not counted.
"""

from __future__ import annotations

from chipbench import kinds


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
    tokens = traffic["batch"] * traffic["seq"]
    f_bb = backbone_forward(mc, traffic["seq"], tokens)
    f_head = head_forward(mc, tokens)
    K = traffic["K"]
    return traffic["nodes"] * (8 * f_bb + (6 * K + 12) * f_head)


def backbone_forward(mc: dict, seq: int, tokens: int) -> float:
    """The blocks' forward FLOPs: each period position's layer, R times."""
    R = mc["num_layers"] // len(mc["pattern"])
    return sum(R * mod.forward_flops(mc, seq, tokens, p)
               for p, mod in enumerate(kinds.modules(mc)))


def head_forward(mc: dict, tokens: int) -> float:
    return 2.0 * mc["d_model"] * mc["vocab_size"] * tokens
