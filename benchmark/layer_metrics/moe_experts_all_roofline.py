"""Expert layer: the grouped products' share of their roofline over ALL of
their time, for a configuration that states its sparse layers by
``num_dense_layers``: as ``moe_experts_bd_roofline`` counts them under
diffusion over blocks, the time under ``hvdt.moe.experts`` (the activation,
the sums) PLUS XLA:TPU's own ``ragged-dot-*`` Mosaic calls, which carry no
scope of the program and are the part that follows the rows landing on the
held experts.  Least time from ``families.laguna.expert_products_cost`` at
the rows a step is expected to land here (tokens x experts per token x
experts held / experts routed), times the sparse layers held."""

from benchmark.families.laguna import expert_products_cost
from benchmark.families.lfm2 import sparse_layers
from benchmark.layer_metrics import per_step, roofline
from benchmark.layer_metrics.moe_experts_bd_roofline import \
    is_grouped_product
from benchmark.phase_split import scope_metric


def read(ctx):
    around = scope_metric(ctx, "hvdt.moe.experts")
    cfg = ctx.config
    if not around or "num_dense_layers" not in cfg:
        return None
    calls = per_step(ctx, is_grouped_product)[0] or 0.0
    tokens = ctx.traffic["per_chip_batch"] * ctx.traffic["seq"]
    rows = (tokens * cfg["num_experts_per_tok"] * cfg["experts"]
            / cfg["num_experts"])
    least, _bound = roofline(*expert_products_cost(
        rows=rows, d_model=cfg["hidden_size"],
        d_ff=cfg["moe_intermediate_size"], experts=cfg["experts"]),
        ctx.peaks)
    return 100.0 * (1e3 * least * sparse_layers(cfg)) / (around + calls)
