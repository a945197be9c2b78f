"""Kernels: the windowed flash forward's share of its roofline: the calls
counted under ``hvdt.kernel.flash_win_fwd`` at the least time one call
needs (``families.laguna.flash_call_cost`` over the visible pairs only,
sum_i min(i + 1, window), at the sliding layers' head count), over their
measured time (``flash_win_fwd_ms``)."""

from benchmark.layer_metrics.flash_full_fwd_roofline import flash_share


def read(ctx):
    return flash_share(ctx, "hvdt.kernel.flash_win_fwd",
                       "sliding_attention", backward=False)
