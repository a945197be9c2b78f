"""Linear mixer: device time per step in the chunked scalar-decay scan
(``hvdt.ssd.scan``: from delta and the log-decays to y with ``D x``),
forward, recompute and backward.  ``ssd_chunk_ms + ssd_state_ms +
ssd_out_ms`` add up to it.  Moves ``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.ssd.scan")
