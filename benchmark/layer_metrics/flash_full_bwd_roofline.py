"""Kernels: the full-causal flash backward's share of its roofline in a
configuration with grouped queries and a head_dim of its own: the calls
counted under ``hvdt.kernel.flash_bwd`` at the least time one call needs
(``families.laguna.flash_call_cost`` at the full layers' head count), over
their measured time (``flash_bwd_ms``)."""

from benchmark.layer_metrics.flash_full_fwd_roofline import flash_share


def read(ctx):
    return flash_share(ctx, "hvdt.kernel.flash_bwd", "full_attention",
                       backward=True)
