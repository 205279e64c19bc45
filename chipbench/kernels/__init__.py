"""One module per compressor name of a traffic mix (``compressor``):
``PATTERN``, a regular expression on the own name of the kernel's
custom call in the profiler trace (the jitted wrapper's name: the
``pallas_call``s carry no ``name=`` yet; checked by hand on a v5e trace), and ``round_bytes(leaf
sizes, dtype bytes, traffic)``, the HBM bytes the kernel's calls in one
round need, from shapes."""
