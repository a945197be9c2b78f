"""Expert layer: device time per step in the sparse feed-forwards
(``hvdt.moe`` under ``hvdt.mlp``: router, sort, gather, grouped products,
weighted return, shared expert), forward, recompute and backward together
(device trace joined to the compiled step's ``op_name``s,
``benchmark/phase_split.py``).  None where the step has no such scope.
Moves ``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.moe") or None
