"""Linear mixer: Mosaic calls a step under the ``hvdt.kernel.eva_*`` names
(``eva_win_fwd`` / ``eva_win_bwd``: the causal flash calls on the aligned
windows; ``eva_sum_fwd`` / ``eva_sum_dq`` / ``eva_sum_dkv``: the
summaries'), counted from the trace's events; 0 where the XLA form of the
aggregation runs.  A count that shows a change of form before the time
does."""

from benchmark.phase_split import scope_calls
from benchmark.trace_reduce import is_mosaic

KERNELS = ("eva_win_fwd", "eva_win_bwd", "eva_sum_fwd", "eva_sum_dq",
           "eva_sum_dkv")


def read(ctx):
    if scope_calls(ctx, "hvdt.eva")[0] is None:
        return None
    return sum(scope_calls(ctx, f"hvdt.kernel.{k}", is_mosaic)[1] or 0
               for k in KERNELS)
