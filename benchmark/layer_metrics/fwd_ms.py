"""Models: device time per step in the forward pass, which is what JAX
traced under ``jvp(`` and neither under ``transpose(`` nor in a
``rematted_computation`` (device trace joined to the compiled step's
``op_name``s, ``benchmark/phase_split.py``).  Moves throughput."""

from benchmark.phase_split import phase_ms


def read(ctx):
    return phase_ms(ctx, "forward")
