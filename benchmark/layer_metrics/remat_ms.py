"""Models: device time per step spent recomputing the forward inside the
backward (``rematted_computation``: every ``jax.checkpoint``-ed block and
loss chunk; device trace joined to the compiled step's ``op_name``s,
``benchmark/phase_split.py``).  Moves throughput; what remat buys shows
in ``peak_hbm_gib``."""

from benchmark.phase_split import phase_ms


def read(ctx):
    return phase_ms(ctx, "remat")
