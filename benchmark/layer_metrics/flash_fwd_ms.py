"""Kernels: device time per step in the flash-attention forward's Mosaic
custom call (device trace).  Today the forward is the step's only Mosaic
call, so every Mosaic event is counted (PERF.md section 3); the split by
kernel name waits for the `tracing` issue.  Moves ``tokens_per_s_chip``
where the kernel is selected."""

from benchmark import trace_reduce
from benchmark.layer_metrics import per_step


def read(ctx):
    return per_step(ctx, trace_reduce.is_mosaic)[0]
