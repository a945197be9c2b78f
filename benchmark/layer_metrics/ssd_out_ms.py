"""Linear mixer: device time per step in what follows the state's loop
(``hvdt.ssd.scan.out``: what the entering states add to y, the sum with
the chunks' own part, ``D x``), forward, recompute and backward.  Moves
``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.ssd.scan.out")
