"""Linear mixer: device time per step in EVA's pooling
(``hvdt.eva.summary``: a chunk's softmax over its keys' products with phi,
the pooled key and value, forward, recompute and backward into k, v, phi
and mu).  Moves ``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.eva.summary")
