"""Expert layer: device time per step moving the products' rows back to
their tokens (``hvdt.moe.dispatch.tokens``: ``_tokens_of_rows``, the rows
gathered by pick and summed a token; in the backward its cotangent rule, a
gather of tokens x picks rows).  With ``moe_rows_ms`` it is the time under
``hvdt.moe.dispatch``.  Moves ``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.moe.dispatch.tokens")
