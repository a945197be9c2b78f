"""Expert layer: routes that pick by score PLUS a selection bias, read
from the compiled step's HLO: the sorting instructions (as
``moe_route_sorts`` finds them) whose resolved ``op_name`` has
``hvdt.moe.route.select_bias`` on its path, each counted once in the text.
The layers of a kind are one ``while`` body and the route runs in the
forward alone (its results are saved across the recompute), so this is a
count a KIND of sparse layer: 2 where an attention layer and a run of
short-convolution layers are sparse, whatever the run's length.  0 where
the program routes and no route takes a bias (the picks are the scores'
own); None where it has nothing under ``hvdt.moe.route``.  Moves
``tokens_per_s_chip``."""

from benchmark.layer_metrics.moe_route_sorts import SCOPE, sorts
from benchmark.phase_split import has_scope, op_names

SELECT = "hvdt.moe.route.select_bias"


def read(ctx):
    names = op_names(ctx.hlo_text)
    if not any(has_scope(n, SCOPE) for n in names.values()):
        return None
    return sum(has_scope(names[name], SELECT)
               for name in sorts(ctx.hlo_text))


