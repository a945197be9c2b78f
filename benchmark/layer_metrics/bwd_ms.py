"""Models: device time per step in the backward pass (``transpose(``, the
recompute left out; device trace joined to the compiled step's
``op_name``s, ``benchmark/phase_split.py``).  Moves throughput."""

from benchmark.phase_split import phase_ms


def read(ctx):
    return phase_ms(ctx, "backward")
