"""Kernels: the windowed flash backward's share of its roofline: the calls
counted under ``hvdt.kernel.flash_win_bwd`` at the least time one call
needs (``families.laguna.flash_call_cost``: five products over the visible
pairs only, dk and dv written per query head as the kernel writes them),
over their measured time (``flash_win_bwd_ms``)."""

from benchmark.layer_metrics.flash_full_fwd_roofline import flash_share


def read(ctx):
    return flash_share(ctx, "hvdt.kernel.flash_win_bwd",
                       "sliding_attention", backward=True)
