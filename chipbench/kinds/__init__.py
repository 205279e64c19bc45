"""One module per layer implementation, found by name.

A configuration's pattern names a kind for each period position ("full",
"mamba", ...).  The configuration file may map its kinds to modules of
this package under the key `KEY`, beside ``model``; without it, `DEFAULT`
applies.  Each module gives, for a block of its kind at period position
``p``:

* ``leaves(mc, R, p)``: the block's leaves as `chipbench.weights.Leaf`s
  stacked over the ``R`` repeats, in the program's layout;
* ``layer(blk, mc, x, mm)``: its plain float32 reference layer on the
  hidden states ``x`` (one repeat's block; ``mm`` a
  `chipbench.reference.matmul.Matmul`);
* ``forward_flops(mc, seq, tokens, p)``: the forward FLOPs of one such
  layer over ``tokens`` tokens in sequences of ``seq``.

``p`` is there because the program decides by period position whether a
block's MLP holds experts (`repro.configs.base.ModelConfig.is_moe_layer`).

A configuration with a kind of its own brings a new module and maps its
kind to it; it edits none of the modules here.
"""

from __future__ import annotations

import importlib

KEY = "kinds"
DEFAULT = {"full": "full", "mamba": "mamba"}


def modules(mc: dict) -> list:
    """The kind module of each period position of ``mc["pattern"]``."""
    names = mc.get(KEY, DEFAULT)
    missing = sorted(set(mc["pattern"]) - set(names))
    if missing:
        raise KeyError(f"no kind module mapped for {missing}; the map is {names}")
    return [importlib.import_module(f"{__name__}.{names[k]}") for k in mc["pattern"]]
