"""Optimizer + exchange: device time per step in the gradient exchange,
the collectives *and* the packing, scaling and unpacking of buckets around
them (``hvdt.exchange``), hidden behind compute or not (device trace
joined to the compiled step's ``op_name``s, ``benchmark/phase_split.py``).
Moves ``tokens_per_s_chip`` in the cells that exchange."""

from benchmark.phase_split import phase_ms


def read(ctx):
    return phase_ms(ctx, "exchange")
