"""Linear mixer: device time per step in EVA's aggregation
(``hvdt.eva.core``: every device event it lowers to, Mosaic or XLA, the
merge of the two softmaxes included), forward, recompute and backward
together.  Moves ``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.eva.core")
