"""Linear mixer: device time per step in what the chunked scan computes
for all chunks at once, before the state's loop (``hvdt.ssd.scan.chunk``:
delta, the running sums of log a, the scores ``C B^T`` once a group, each
head's masked pairs and their product with x, each chunk's own
contribution to the state), forward, recompute and backward.  With
``ssd_state_ms`` and ``ssd_out_ms`` it adds up to ``ssd_scan_ms``.  Moves
``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.ssd.scan.chunk")
