"""Linear mixer: device time per step in the causal depthwise convolution
over the q, k and v channels and its silu (``hvdt.gdn.conv``), forward,
recompute and backward.  Moves ``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.gdn.conv")
