"""Models: device time per step in the embedding lookup (``hvdt.embed``:
the gather of the tokens' rows and, in the backward, the scatter-add of
their cotangents into ``[vocab, d]``).  Moves ``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.embed")
