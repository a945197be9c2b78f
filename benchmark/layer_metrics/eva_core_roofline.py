"""Linear mixer: EVA's aggregation's share of its roofline.  Least time
from shapes (``families.evabyte.eva_core_cost``: two products forward,
again in the recompute, five backward, over the VISIBLE pairs of the two
masks only; q, o at the heads held, k, v and the summaries read once a
pass), times the layers held, over the time under ``hvdt.eva.core``
(``eva_core_ms``).  It reads the same work whatever implements it: a form
that computes tiles without a visible pair reads low, and none can read
over 100%."""

from benchmark.families.evabyte import eva_core_cost
from benchmark.layer_metrics import roofline
from benchmark.phase_split import scope_metric


def read(ctx):
    ms = scope_metric(ctx, "hvdt.eva.core")
    cfg = ctx.config
    if not ms or cfg.get("attention_class") != "eva":
        return None
    shape = dict(
        batch=ctx.traffic["per_chip_batch"], seq=ctx.traffic["seq"],
        heads=cfg["heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        window=cfg["window_size"], chunk=cfg["chunk_size"])
    forward, _ = roofline(*eva_core_cost(**shape), ctx.peaks)
    backward, _ = roofline(*eva_core_cost(**shape, backward=True), ctx.peaks)
    return 100.0 * 1e3 * cfg["layers"] * (2 * forward + backward) / ms
