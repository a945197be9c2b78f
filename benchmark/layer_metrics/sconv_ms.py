"""Linear mixer: device time per step in the short-convolution sublayer
(``hvdt.sconv``: pre-norm, the input projection's three column blocks, the
two gates and the taps, the output projection), forward, recompute and
backward together (device trace joined to the compiled step's ``op_name``s,
``benchmark/phase_split.py``).  A sibling of ``attention_ms``, ``gdn_ms``
and ``ssd_ms``.  Moves ``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.sconv")
