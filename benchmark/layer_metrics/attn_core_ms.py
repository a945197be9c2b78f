"""Models: device time per step in the call of ``ops.attention.attention``
(``hvdt.attention.core``): the flash kernels and everything
``ops/attention.py`` and ``ops/pallas_kernels.py`` put around them (layout
copies, ``delta``, dk / dv summed over a group), or XLA attention where
that runs; forward, recompute and backward.  ``attn_surround_ms`` is the
part of it outside the kernels.  Moves ``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.attention.core")
