"""Kernels: the block-mask flash forward's share of its roofline: the calls
counted under ``hvdt.kernel.flash_bd_fwd`` at the least time one call
needs (``families.sdar.flash_bd_call_cost``: two products over the VISIBLE
pairs only, L^2 + L B a head over the 2 L rows of a sequence), over their
measured time (``flash_bd_fwd_ms``).  A form of the kernel that computes
tiles without a visible pair reads low here, and none can read over 100%.

``bd_share`` serves the backward's reader too."""

from benchmark.families.sdar import flash_bd_call_cost
from benchmark.layer_metrics import roofline
from benchmark.phase_split import scope_calls
from benchmark.trace_reduce import is_mosaic


def bd_share(ctx, scope: str, *, backward: bool):
    """Percent of its roofline of the block-mask flash kernel under
    ``scope``; None where the step has no such call or the configuration
    no block length."""
    ms, calls = scope_calls(ctx, scope, is_mosaic)
    cfg = ctx.config
    if not ms or "block_length" not in cfg:
        return None
    least, _bound = roofline(*flash_bd_call_cost(
        batch=ctx.traffic["per_chip_batch"], seq=ctx.traffic["seq"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        block=cfg["block_length"], backward=backward), ctx.peaks)
    return 100.0 * (1e3 * least * calls) / ms


def read(ctx):
    return bd_share(ctx, "hvdt.kernel.flash_bd_fwd", backward=False)
