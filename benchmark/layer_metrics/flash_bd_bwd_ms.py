"""Kernels: device time per step in the block-mask flash-attention
backward's Mosaic calls, found by the name the program gives them
(``hvdt.kernel.flash_bd_bwd``; device trace joined to the compiled step's
``op_name``s, ``benchmark/phase_split.py``).  Moves ``tokens_per_s_chip``."""

from benchmark.phase_split import scope_calls
from benchmark.trace_reduce import is_mosaic


def read(ctx):
    return scope_calls(ctx, "hvdt.kernel.flash_bd_bwd", is_mosaic)[0]
