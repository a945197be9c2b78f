"""Linear mixer: device time per step in the chunked scan's sequential
part (``hvdt.gdn.scan.state``: the ``lax.scan`` over the chunks that
carries the state; its ``while`` forward, in the recompute and reversed in
the backward; ``gdn_scan_steps`` counts its trips).  Moves
``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.gdn.scan.state")
