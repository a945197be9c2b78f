"""Kernels: Mosaic calls a step under ``hvdt.kernel.flash_bd_fwd`` and
``hvdt.kernel.flash_bd_bwd`` together, counted from the trace's events:
the counter that the block-mask kernels engage.  Three a layer where one
call covers both streams (forward, recompute, backward): 18 at six
layers."""

from benchmark.phase_split import scope_calls
from benchmark.trace_reduce import is_mosaic


def read(ctx):
    counts = [scope_calls(ctx, f"hvdt.kernel.flash_bd_{d}", is_mosaic)[1]
              for d in ("fwd", "bwd")]
    return sum(c for c in counts if c) or None
