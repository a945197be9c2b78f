"""Models: device time per step that no name reaches: the caller's own
operations and the copies the compiler adds to the entry computation.  It
rises when a refactor drops a scope (device trace joined to the compiled
step's ``op_name``s, ``benchmark/phase_split.py``).  Moves throughput."""

from benchmark.phase_split import phase_ms


def read(ctx):
    return phase_ms(ctx, "unscoped")
