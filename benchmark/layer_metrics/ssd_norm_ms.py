"""Linear mixer: device time per step in the gated norm across a group's
channels (``hvdt.ssd.norm``: ``RMS(y silu(z)) w``, float32), forward,
recompute and backward.  Moves ``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.ssd.norm")
