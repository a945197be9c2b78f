"""Models: what the attention kernels cost around themselves: device time
per step of the leaf events under ``hvdt.attention.core`` that are not
Mosaic calls (layout copies and transposes, ``delta``, the float32 sums of
dk / dv over a query group; all of XLA attention where no kernel runs).
``attn_core_ms`` less this is the kernels' own time.  Moves
``tokens_per_s_chip``."""

from benchmark import trace_reduce
from benchmark.phase_split import scope_calls


def read(ctx):
    return scope_calls(ctx, "hvdt.attention.core",
                       lambda op: not trace_reduce.is_mosaic(op))[0]
