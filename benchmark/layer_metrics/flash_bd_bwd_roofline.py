"""Kernels: the block-mask flash backward's share of its roofline: the
calls counted under ``hvdt.kernel.flash_bd_bwd`` at the least time one
call needs (``families.sdar.flash_bd_call_cost``: five products over the
visible pairs only, dk and dv written per query head as the kernel writes
them), over their measured time (``flash_bd_bwd_ms``)."""

from benchmark.layer_metrics.flash_bd_fwd_roofline import bd_share


def read(ctx):
    return bd_share(ctx, "hvdt.kernel.flash_bd_bwd", backward=True)
