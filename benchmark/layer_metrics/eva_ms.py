"""Linear mixer: device time per step in EVA attention's core (``hvdt.eva``,
inside ``hvdt.attention.core``: the pooling of the chunk summaries and the
aggregation, the joined softmax over a window's keys and the earlier
windows' summaries), forward, recompute and backward together (device
trace joined to the compiled step's ``op_name``s,
``benchmark/phase_split.py``).  Moves ``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.eva")
