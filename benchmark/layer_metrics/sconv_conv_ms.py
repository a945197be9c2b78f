"""Linear mixer: device time per step in the short convolution's two gates
and its taps (``hvdt.sconv.conv``: ``C * conv(B * X)``, one pass over the
three projected blocks), forward, recompute and backward.  Moves
``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.sconv.conv")
