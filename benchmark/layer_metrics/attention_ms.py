"""Models: device time per step in the attention sublayer
(``hvdt.attention``: pre-norm, projections, RoPE, scores or kernel, output
projection), forward, recompute and backward together (device trace joined
to the compiled step's ``op_name``s, ``benchmark/phase_split.py``).  Moves
``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.attention")
