"""Linear mixer: device time per step in what the chunked scan computes
for all chunks at once, before the state's loop (``hvdt.gdn.scan.chunk``:
the L2 norms, the decays' cumulative sum and ratios, ``A``, its inverse
(the ``hvdt.kernel.gdn_inverse`` call is under it), ``attn``, ``T (beta
V)``, ``W``, ``K_out``), forward, recompute and backward.  With
``gdn_state_ms`` and ``gdn_out_ms`` it adds up to ``gdn_scan_ms``.  Moves
``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.gdn.scan.chunk")
