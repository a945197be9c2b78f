"""Models: median device duration of the step program (device trace,
``XLA Modules`` line), on the slowest device.  Moves the cell's
throughput."""

import statistics

from benchmark import trace_reduce
from benchmark.layer_metrics import worst


def _median_step(dev):
    steps = trace_reduce.step_modules(dev)
    return 1e3 * statistics.median(s.seconds for s in steps) if steps else None


def read(ctx):
    return worst(ctx, _median_step)
