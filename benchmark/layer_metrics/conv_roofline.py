"""Kernels: the convolutions' share of their roofline.  Least time per
step from shapes, summed over every convolution of the network forward
and backward, each at max(operations / peak FLOP/s, bytes / peak HBM
B/s), over the measured ``conv_ms``.

Per convolution and image batch N, in the compute dtype (2 bytes):
forward reads the input and the weights and writes the output; the
input-gradient convolution reads the output gradient and the weights and
writes the input gradient; the weight-gradient convolution reads the
input and the output gradient and writes the weight gradient.  The stem
has no input gradient.  What XLA fuses into a convolution (batch-norm
statistics, ReLU) adds traffic the roofline does not grant."""

from benchmark.families.resnet import conv_shapes
from benchmark.layer_metrics import roofline
from benchmark.layer_metrics import conv_ms

BYTES = 2


def least_seconds(config: dict, batch: int, peaks: dict) -> float:
    least = 0.0
    for i, (kh, kw, cin, cout, out, side) in enumerate(conv_shapes(config)):
        ops = 2.0 * kh * kw * cin * cout * out * out * batch
        x = batch * side * side * cin * BYTES
        y = batch * out * out * cout * BYTES
        w = kh * kw * cin * cout * BYTES
        passes = [(ops, x + w + y), (ops, x + y + w)]   # forward, weight grad
        if i:
            passes.append((ops, y + w + x))             # input grad
        least += sum(roofline(o, b, peaks)[0] for o, b in passes)
    return least


def read(ctx):
    ms = conv_ms.read(ctx)
    if not ms or "image_size" not in ctx.config:
        return None
    least = least_seconds(ctx.config, ctx.traffic["per_chip_batch"],
                          ctx.peaks)
    return 100.0 * (1e3 * least) / ms
