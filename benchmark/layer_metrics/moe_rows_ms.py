"""Expert layer: device time per step moving tokens into the sorted row
buffer before the grouped products (``hvdt.moe.dispatch.rows``:
``_rows_of_tokens``, a gather of tokens x picks rows; in the backward its
cotangent rule, the rows gathered back and summed a token).  Moves
``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.moe.dispatch.rows")
