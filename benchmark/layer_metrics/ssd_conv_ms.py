"""Linear mixer: device time per step in the causal depthwise convolution
over the x, B and C channels, its bias and its silu (``hvdt.ssd.conv``),
forward, recompute and backward.  Moves ``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.ssd.conv")
