"""Expert layer: sorts in the route, read from the compiled step's HLO:
instructions that sort or pick the largest, whose resolved ``op_name``
(``phase_split.op_names``) has ``hvdt.moe.route`` on its path, each counted
once in the text (the layers of a kind are one ``while`` body each way, so
this is a count a kind of sparse layer and pass, not a layer).  XLA:TPU
gives ``lax.top_k`` no opcode of its own: it is a ``sort`` of the whole
[tokens, experts] row pair (its ``op_name`` ends in ``top_k``) and a slice
of the first k; XLA:CPU keeps a ``topk`` instruction, and older lowerings a
custom call to ``TopK``: all three are counted.  A route computed in the
forward alone has three a kind of sparse layer (the top-k and the two
argsorts of the picks' keys); one that the layer's recompute runs again has
six.  A count: it repeats exactly, and a route brought back into the
recompute shows here before it shows in time.  None where the program has
nothing under ``hvdt.moe.route``.  Moves ``tokens_per_s_chip``."""

from benchmark import trace_reduce
from benchmark.phase_split import has_scope, op_names

SCOPE = "hvdt.moe.route"


def sorts(hlo_text: str) -> list:
    """The names of the sorting instructions under ``hvdt.moe.route``."""
    names = op_names(hlo_text)
    found = []
    for line in hlo_text.splitlines():
        name, opcode, detail = trace_reduce.parse_instruction(
            line.strip().removeprefix("ROOT "))
        sorting = opcode in ("sort", "topk") or (
            opcode == "custom-call" and detail == "TopK")
        if sorting and has_scope(names.get(name, ""), SCOPE):
            found.append(name)
    return found


def read(ctx):
    if not any(has_scope(n, SCOPE) for n in op_names(ctx.hlo_text).values()):
        return None
    return len(sorts(ctx.hlo_text))
