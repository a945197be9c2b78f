"""Optimizer + exchange: operand bytes of the all-reduces over all of the
cell's chips in the compiled step's HLO (a copy of
``chip_smoke.hlo_allreduces``).  A count: it repeats exactly."""

import re

_SHAPE_RE = re.compile(r"\b(pred|[a-z]+\d+)\[([\d,]*)\]")
_ALLREDUCE_RE = re.compile(
    r"=\s+(?P<shape>.*?)\s+all-reduce(?:-start)?\((?P<rest>.*)$")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
                "u64": 8}


def hlo_allreduces(hlo_text: str):
    """(operand bytes, replica-group size) of every all-reduce (or
    all-reduce-start) in an HLO module's text."""
    out = []
    for line in hlo_text.splitlines():
        m = _ALLREDUCE_RE.search(line)
        if not m:
            continue
        nbytes = 0
        for dtype, dims in _SHAPE_RE.findall(m.group("shape")):
            count = 1
            for d in filter(None, dims.split(",")):
                count *= int(d)
            nbytes += count * _DTYPE_BYTES[dtype]
        rest = m.group("rest")
        g = re.search(r"replica_groups=\{\{([\d,]*)\}", rest)
        if g:
            group = len(g.group(1).split(","))
        else:
            g = re.search(r"replica_groups=\[(\d+),(\d+)\]", rest)
            group = int(g.group(2)) if g else 0
        out.append((nbytes, group))
    return out


def read(ctx):
    found = hlo_allreduces(ctx.hlo_text)
    over_all = [b for b, g in found if g == ctx.chips]
    return sum(over_all) if over_all else None
