"""Expert layer: the grouped products' share of their roofline.  Least
time from shapes (``families.laguna.expert_products_cost``: twelve
products a step of one sparse layer on the rows a step is expected to land
here, tokens x experts per token x experts held / experts routed, against
the held experts' bf16 matrices read once a pass), times the sparse layers
held, over the time under ``hvdt.moe.experts`` (``moe_experts_ms``)."""

from benchmark.families.laguna import expert_products_cost
from benchmark.layer_metrics import roofline
from benchmark.phase_split import scope_metric


def read(ctx):
    ms = scope_metric(ctx, "hvdt.moe.experts")
    cfg = ctx.config
    if not ms or "mlp_layer_types" not in cfg:
        return None
    tokens = ctx.traffic["per_chip_batch"] * ctx.traffic["seq"]
    rows = (tokens * cfg["num_experts_per_tok"] * cfg["experts"]
            / cfg["num_experts"])
    sparse = cfg["mlp_layer_types"][:cfg["layers"]].count("sparse")
    least, _bound = roofline(*expert_products_cost(
        rows=rows, d_model=cfg["hidden_size"],
        d_ff=cfg["moe_intermediate_size"], experts=cfg["experts"]),
        ctx.peaks)
    return 100.0 * (1e3 * least * sparse) / ms
