"""Kernels: the full-causal flash forward's share of its roofline in a
configuration with grouped queries and a head_dim of its own
(``flash_fwd_roofline``'s reader divides ``d_model`` by ``heads``): the
calls counted under ``hvdt.kernel.flash_fwd`` at the least time one call
needs (``families.laguna.flash_call_cost`` at the full layers' head count
over their kv heads), over their measured time (``flash_fwd_ms``).

``flash_share`` serves the three sibling readers too (the windowed pair,
the full backward): one layer type's kernel under one scope."""

from benchmark.families.laguna import flash_call_cost
from benchmark.layer_metrics import roofline
from benchmark.phase_split import scope_calls
from benchmark.trace_reduce import is_mosaic


def flash_share(ctx, scope: str, layer_type: str, *, backward: bool):
    """Percent of its roofline of the flash kernel that ``layer_type``'s
    layers run under ``scope``; None where the step has no such call or
    the configuration no such layers."""
    ms, calls = scope_calls(ctx, scope, is_mosaic)
    cfg = ctx.config
    types = cfg.get("layer_types", [])[:cfg.get("layers", 0)]
    if not ms or layer_type not in types:
        return None
    least, _bound = roofline(*flash_call_cost(
        batch=ctx.traffic["per_chip_batch"], seq=ctx.traffic["seq"],
        heads=cfg["num_attention_heads_per_layer"][types.index(layer_type)],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=cfg["sliding_window"]
        if layer_type == "sliding_attention" else None,
        backward=backward), ctx.peaks)
    return 100.0 * (1e3 * least * calls) / ms


def read(ctx):
    return flash_share(ctx, "hvdt.kernel.flash_fwd", "full_attention",
                       backward=False)
