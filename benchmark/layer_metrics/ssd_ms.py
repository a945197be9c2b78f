"""Linear mixer: device time per step in the Mamba-2 sublayer (``hvdt.ssd``:
pre-norm, the input projection, the convolution, the chunked scan, the
gated norm, the output projection), forward, recompute and backward
together (device trace joined to the compiled step's ``op_name``s,
``benchmark/phase_split.py``).  A sibling of ``attention_ms`` and
``gdn_ms``.  Moves ``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.ssd")
