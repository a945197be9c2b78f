"""Optimizer + exchange: device time per step in the wrapped optax
transformation's update (``hvdt.optimizer``; ``optax.apply_updates`` is
the caller's and counts where the compiler fused it; device trace joined
to the compiled step's ``op_name``s, ``benchmark/phase_split.py``).  Moves
throughput."""

from benchmark.phase_split import phase_ms


def read(ctx):
    return phase_ms(ctx, "optimizer")
