"""Expert layer: the grouped products' share of their roofline under
diffusion over blocks, as ``moe_experts_roofline`` with the rows BOTH
streams send: 2 x tokens x experts per token x experts held / experts
routed (``moe_experts_roofline``'s reader takes a token for one row, half
of such a cell's).  Least time from ``families.laguna
.expert_products_cost``, times the sparse layers held.

Over ALL of the grouped products' time: XLA:TPU lowers ``ragged_dot`` to
Mosaic calls it names ``ragged-dot-none`` (and ``ragged-dot-metadata``)
and gives its own ``op_name``, so the program's scope is not on their path
and ``moe_experts_ms`` reads only what is left around them under
``hvdt.moe.experts`` (the activation, the sums).  The calls are the part
that follows the rows landing on the held experts.  Only ``moe_held_experts``
calls ``ragged_dot``, so the name picks the expert layer's calls alone."""

from benchmark.families.laguna import expert_products_cost
from benchmark.layer_metrics import per_step, roofline
from benchmark.phase_split import scope_metric
from benchmark.trace_reduce import is_mosaic


def is_grouped_product(op) -> bool:
    return is_mosaic(op) and op.name.startswith("ragged-dot")


def read(ctx):
    around = scope_metric(ctx, "hvdt.moe.experts")
    cfg = ctx.config
    if not around or "block_length" not in cfg:
        return None
    calls = per_step(ctx, is_grouped_product)[0] or 0.0
    tokens = ctx.traffic["per_chip_batch"] * ctx.traffic["seq"]
    rows = (2 * tokens * cfg["num_experts_per_tok"] * cfg["experts"]
            / cfg["num_experts"])
    sparse = cfg["mlp_layer_types"][:cfg["layers"]].count("sparse")
    least, _bound = roofline(*expert_products_cost(
        rows=rows, d_model=cfg["hidden_size"],
        d_ff=cfg["moe_intermediate_size"], experts=cfg["experts"]),
        ctx.peaks)
    return 100.0 * (1e3 * least * sparse) / (around + calls)
