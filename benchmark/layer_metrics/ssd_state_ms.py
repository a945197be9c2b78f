"""Linear mixer: device time per step in the state's loop over the chunks
(``hvdt.ssd.scan.state``: the ``lax.scan`` that carries the float32 state,
sequence / chunk trips, forward, recompute and reverse).  Moves
``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.ssd.scan.state")
