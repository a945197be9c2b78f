"""Expert layer: device time per step in the held experts' grouped
products (``hvdt.moe.experts``: ``jax.lax.ragged_dot`` over the rows sorted
by expert, three products a pass and the activation between them; forward,
recompute and backward).  Moves ``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.moe.experts") or None
