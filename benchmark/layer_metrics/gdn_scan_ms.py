"""Linear mixer: device time per step in the gated delta rule's chunked
scan (``hvdt.gdn.scan``: from the L2 norms of q and k to O; the products
inside the chunks and the state's loop over them), forward, recompute and
backward.  Moves ``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.gdn.scan")
