"""Kernels: the flash backward's share of its roofline.  Least time from
shapes (``layer_metrics.flash_bwd_call_cost``: max(operations / peak
FLOP/s, bytes / peak HBM B/s) per call), times the calls per step counted
under ``hvdt.kernel.flash_bwd`` in the trace, over their measured time
(``flash_bwd_ms``)."""

from benchmark.layer_metrics import flash_bwd_call_cost, flash_roofline_pct
from benchmark.phase_split import scope_calls
from benchmark.trace_reduce import is_mosaic


def read(ctx):
    ms, calls = scope_calls(ctx, "hvdt.kernel.flash_bwd", is_mosaic)
    return flash_roofline_pct(ctx, ms, calls, flash_bwd_call_cost)
