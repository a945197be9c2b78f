"""Linear mixer: the short-convolution mixers' share of their roofline.
Least time from shapes (``families.lfm2.sconv_cost``: the two projections'
products forward, again in the recompute and twice in the backward,
compute-bound at these widths; the same whatever implements the mixer),
times the short-convolution layers held, over the time under ``hvdt.sconv``
(``sconv_ms``).

Of the WHOLE mixer, not of its gates and taps alone: XLA fuses most of
``C * conv(B * X)`` into the neighbouring products' fusions (the scope
``hvdt.sconv.conv`` held 3.25 ms a step on the chip where the pass's own
bytes need 5.24: PERF.md, PR 48), so a share of ``sconv_conv_ms`` would
leave out part of the work it counts and read over 100%."""

from benchmark.families.lfm2 import conv_layers, sconv_cost
from benchmark.layer_metrics import roofline
from benchmark.phase_split import scope_metric


def read(ctx):
    ms = scope_metric(ctx, "hvdt.sconv")
    cfg = ctx.config
    if not ms or "conv_L_cache" not in cfg:
        return None
    tokens = ctx.traffic["per_chip_batch"] * ctx.traffic["seq"]
    least, _bound = roofline(*sconv_cost(cfg, tokens=tokens), ctx.peaks)
    return 100.0 * (1e3 * least * conv_layers(cfg)) / ms
