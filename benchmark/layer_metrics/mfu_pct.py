"""Models: model FLOP/s utilization.  Forward + backward operations per
token or image from shapes (the family's count: causal half of the score
square, recompute not counted, output head once) x the measured window's
throughput per chip / the chip's published bf16 peak.  Throughput times a
constant, so it moves with the cell's throughput and nothing else."""


def read(ctx):
    return (100.0 * ctx.family.flops_per_unit * ctx.throughput
            / ctx.peaks["bf16_flops_per_s"])
