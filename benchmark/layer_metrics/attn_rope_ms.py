"""Models: device time per step in q / k norm (where the configuration has
one) and the rotary embedding of q and k (``hvdt.attention.rope``),
forward, recompute and backward: elementwise passes over ``[B, L, H, D]``
in whatever layout XLA gives them.  Moves ``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.attention.rope")
