"""Linear mixer: the chunked scan's share of its roofline.  Least time
from shapes (``families.qwen3_next.scan_cost``: the scan's products
forward, again in the recompute and twice in the backward; q, k, v, g,
beta read and o written a pass, the chunk-boundary states to and from
HBM), times the linear layers held, over the time under ``hvdt.gdn.scan``
(``gdn_scan_ms``)."""

from benchmark.families.qwen3_next import is_full, scan_cost
from benchmark.layer_metrics import roofline
from benchmark.phase_split import scope_metric


def read(ctx):
    ms = scope_metric(ctx, "hvdt.gdn.scan")
    cfg = ctx.config
    if not ms or "full_attention_interval" not in cfg:
        return None
    tokens = ctx.traffic["per_chip_batch"] * ctx.traffic["seq"]
    linear = sum(not is_full(cfg, i) for i in range(cfg["layers"]))
    least, _bound = roofline(*scan_cost(cfg, tokens=tokens), ctx.peaks)
    return 100.0 * (1e3 * least * linear) / ms
