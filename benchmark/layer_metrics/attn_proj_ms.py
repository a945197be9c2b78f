"""Models: device time per step in the attention sublayer's four
projections (``hvdt.attention.qkv``: the ``wq``, ``wk``, ``wv`` products
with their reshapes and the split of an elementwise gate, plus
``hvdt.attention.out``: the ``wo`` product), forward, recompute and
backward, the weight gradients written into the stacked leaves with them.
A fusion takes the name of the matmul it fuses, so RoPE or a gate fused
onto a projection's output counts here.  Moves ``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    parts = [scope_metric(ctx, "hvdt.attention.qkv"),
             scope_metric(ctx, "hvdt.attention.out")]
    return sum(p or 0.0 for p in parts) or None
