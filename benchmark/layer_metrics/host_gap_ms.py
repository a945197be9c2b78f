"""Step pipeline: mean gap on the device between the end of one step
program and the start of the next (device trace, ``XLA Modules`` line), on
the device where it is longest.  Moves the cell's throughput."""

from benchmark import trace_reduce
from benchmark.layer_metrics import worst


def _mean_gap(dev):
    steps = trace_reduce.step_modules(dev)
    gaps = [max(0.0, b.start - a.end) for a, b in zip(steps, steps[1:])]
    return 1e3 * sum(gaps) / len(gaps) if gaps else None


def read(ctx):
    return worst(ctx, _mean_gap)
