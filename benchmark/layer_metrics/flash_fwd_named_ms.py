"""Kernels: device time per step in the flash-attention forward's Mosaic
call, found by its name (``hvdt.kernel.flash_fwd``) and not, as
``flash_fwd_ms`` does, by being the step's only Mosaic call; forward and
recompute instances together (device trace joined to the compiled step's
``op_name``s, ``benchmark/phase_split.py``).  Moves ``tokens_per_s_chip``
where the kernel is selected."""

from benchmark import trace_reduce
from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.kernel.flash_fwd",
                        trace_reduce.is_mosaic)
