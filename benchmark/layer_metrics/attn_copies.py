"""Models: relayouts in the attention sublayer, read from the compiled
step's HLO: instructions of opcode ``copy`` or ``transpose``, bare or
inside a fusion, whose resolved ``op_name`` (``phase_split.op_names``) has
``hvdt.attention`` on its path, each counted once in the text (the layers
are one ``while`` body each way, so this is a count a layer kind, forward +
recompute + backward, not a layer).  A count: it repeats exactly, and a
layout change shows here before it shows in time.  None where the program
has nothing under ``hvdt.attention``.  Moves ``tokens_per_s_chip``."""

from benchmark import trace_reduce
from benchmark.phase_split import has_scope, op_names

SCOPE = "hvdt.attention"


def relayouts(hlo_text: str) -> list:
    """The names of the ``copy`` and ``transpose`` instructions under
    ``hvdt.attention``."""
    names = op_names(hlo_text)
    found = []
    for line in hlo_text.splitlines():
        name, opcode, _ = trace_reduce.parse_instruction(
            line.strip().removeprefix("ROOT "))
        if opcode in ("copy", "transpose") and has_scope(
                names.get(name, ""), SCOPE):
            found.append(name)
    return found


def read(ctx):
    if not any(has_scope(n, SCOPE) for n in op_names(ctx.hlo_text).values()):
        return None
    return len(relayouts(ctx.hlo_text))
