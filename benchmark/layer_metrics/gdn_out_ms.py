"""Linear mixer: device time per step after the state's loop
(``hvdt.gdn.scan.out``: ``q_in``, the two batched products that give ``O``
from the chunks' entering states and ``U``, the un-chunking), forward,
recompute and backward.  Moves ``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.gdn.scan.out")
