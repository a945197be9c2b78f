"""Expert layer: Mosaic calls a step under ``hvdt.kernel.moe_sum_rows``,
counted from the trace's events: the counter that the experts' rows go
back to their tokens by the kernel and not by XLA's gather and sum.  Two a
sparse layer (the forward's rows to tokens, the backward of tokens to
rows; the recompute's copy of the first is dead code): 8 at four sparse
layers, 12 at six.  None on a program that has no such call."""

from benchmark.phase_split import scope_calls
from benchmark.trace_reduce import is_mosaic


def read(ctx):
    return scope_calls(ctx, "hvdt.kernel.moe_sum_rows", is_mosaic)[1] or None
