"""Kernels: the flash forward's share of its roofline.  Least time from
shapes — max(operations / peak FLOP/s, bytes / peak HBM B/s) per call, the
calls per step counted in the trace — over the measured time.

Per call on [B, L, H, D] in bf16, causal: the score and value products
over the causal half of the square, 2 * B*H*L*L*D operations; q, k, v read
and o written once, 4 * B*L*H*D * 2 bytes (the softmax statistics are
L/D-th of that and left out)."""

from benchmark import trace_reduce
from benchmark.layer_metrics import per_step, roofline


def call_cost(batch: int, seq: int, heads: int, head_dim: int):
    ops = 2.0 * batch * heads * seq * seq * head_dim
    nbytes = 4.0 * batch * seq * heads * head_dim * 2
    return ops, nbytes


def read(ctx):
    ms, calls = per_step(ctx, trace_reduce.is_mosaic)
    if not ms or "seq" not in ctx.traffic:
        return None
    cfg = ctx.config
    ops, nbytes = call_cost(ctx.traffic["per_chip_batch"], ctx.traffic["seq"],
                            cfg["heads"], cfg["d_model"] // cfg["heads"])
    least, _bound = roofline(ops, nbytes, ctx.peaks)     # compute-bound
    return 100.0 * (1e3 * least * calls) / ms
