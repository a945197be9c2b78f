"""Optimizer + exchange: per step, the time a collective is in flight on
a device while no compute operation runs there (device trace), on the
device where it is longest.  Moves ``tokens_per_s_chip`` in the cells
that exchange."""

from benchmark import trace_reduce
from benchmark.layer_metrics import steps_on, worst


def _exposed(dev):
    if not steps_on(dev) or not any(map(trace_reduce.is_collective,
                                        dev.ops)):
        return None
    return 1e3 * trace_reduce.exposed_collective_seconds(dev) / steps_on(dev)


def read(ctx):
    return worst(ctx, _exposed)
