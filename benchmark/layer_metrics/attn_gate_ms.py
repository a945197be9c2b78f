"""Models: device time per step in the attention output's gate
(``hvdt.attention.gate``: a gate a head from the layer's input, ``wg``
product, sigmoid, multiply; or an elementwise one from the query
projection's second half), forward, recompute and backward.  Moves
``tokens_per_s_chip`` where the configuration has a gate."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.attention.gate")
