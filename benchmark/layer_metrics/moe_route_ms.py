"""Expert layer: device time per step of the route (``hvdt.moe.route``:
the router's float32 product, the score, the top-k, the two sorts of tokens
x picks keys, the rows' weights, and in the backward the route's cotangent
rule), which ``moe_dispatch_ms`` holds beside the two moves and which was
read by difference until this reader.  Whether a layer's recompute runs the
route again shows here as well as in ``moe_route_sorts``.  Moves
``tokens_per_s_chip``."""

from benchmark.phase_split import scope_metric


def read(ctx):
    return scope_metric(ctx, "hvdt.moe.route")
