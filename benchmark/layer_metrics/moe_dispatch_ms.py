"""Expert layer: device time per step around the grouped products:
``hvdt.moe.route`` (router in float32, scores, top-k, the sort by expert)
plus ``hvdt.moe.dispatch`` (rows gathered into the sorted buffer before
the products, gathered back and weighted after them).  The buffer's static
bound is tokens x experts per token rows, so this part does not shrink
with the rows that landed here.  Moves ``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    parts = [scope_metric(ctx, "hvdt.moe.route"),
             scope_metric(ctx, "hvdt.moe.dispatch")]
    return sum(p or 0.0 for p in parts) or None
