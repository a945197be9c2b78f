"""Linear mixer: device time per step in the Mamba-2 mixer's two
projections (``hvdt.ssd.proj``: ``w_in`` on its column blocks [z | x B C |
dt] and ``w_out``, with their weight gradients), forward, recompute and
backward.  Moves ``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.ssd.proj")
