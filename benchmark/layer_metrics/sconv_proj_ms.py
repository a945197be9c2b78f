"""Linear mixer: device time per step in the short convolution's two
projections (``hvdt.sconv.in``: ``w_in`` on its column blocks [B | C | X];
``hvdt.sconv.out``: ``w_out``; with their weight gradients), forward,
recompute and backward.  None where the program has neither scope.  Moves
``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    parts = [scope_metric(ctx, "hvdt.sconv.in"),
             scope_metric(ctx, "hvdt.sconv.out")]
    found = [ms for ms in parts if ms is not None]
    return sum(found) if found else None
