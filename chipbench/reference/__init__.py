"""Plain reference of what a C2DFB cell computes, in straightforward
``jax.numpy``, independent of the program under test: it imports nothing
from ``repro`` and takes nothing the program made.  Weights come from
`chipbench.weights` and tokens from `chipbench.tokens`, from the seed;
each layer is its kind module's ``layer`` (`chipbench.kinds`).

Math is float32 with every matrix product at ``Precision.HIGHEST``; state
is stored in the configuration's dtype (bf16), as the configuration
states.  ``Matmul("fp8")`` is the control: every matrix product's operands
(forward and backward) rounded to float8_e4m3 with a per-tensor scale.
"""
