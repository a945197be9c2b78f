"""Kernels: device time per step in convolutions (device trace).  The
trace names a fusion, not what it fuses, so the instructions that are or
fuse a convolution are read from the compiled step's HLO text
(``Context.convolutions``) and their leaf events summed.  Moves
``images_per_s_chip``."""

from benchmark.layer_metrics import per_step


def read(ctx):
    if ctx.trace is None:
        return None
    return per_step(ctx, lambda op: op.name in ctx.convolutions)[0]
