"""Linear mixer: the chunked state-space scan's share of its roofline.
Least time from shapes (``families.granite_hybrid.ssd_scan_cost``: the
scan's products forward, again in the recompute and twice in the backward;
x, B, C, delta read and y written a pass, the chunk-boundary states to and
from HBM; the same whatever implements the scan), times the state-space
layers held, over the time under ``hvdt.ssd.scan`` (``ssd_scan_ms``)."""

from benchmark.families.granite_hybrid import ssd_scan_cost
from benchmark.layer_metrics import roofline
from benchmark.phase_split import scope_metric


def read(ctx):
    ms = scope_metric(ctx, "hvdt.ssd.scan")
    cfg = ctx.config
    if not ms or "mamba_n_heads" not in cfg:
        return None
    tokens = ctx.traffic["per_chip_batch"] * ctx.traffic["seq"]
    layers = cfg["layer_types"][:cfg["layers"]].count("mamba")
    least, _bound = roofline(*ssd_scan_cost(cfg, tokens=tokens), ctx.peaks)
    return 100.0 * (1e3 * least * layers) / ms
