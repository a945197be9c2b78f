"""Device: 1 - (union of the leaf operations' intervals) / traced window,
on the idlest device.  The window runs from the first to the last device
event of the trace.  Moves the cell's throughput."""

from benchmark import trace_reduce
from benchmark.layer_metrics import devices


def read(ctx):
    busy = [trace_reduce.busy_seconds(d) for d in devices(ctx)]
    if not busy:
        return None
    lo, hi = trace_reduce.window(ctx.trace)
    return 100.0 * (1.0 - min(busy) / (hi - lo))
