"""Split a traced step by what the program itself says each instruction
is: forward, recompute, backward, optimizer, exchange, or nothing at all.

The program names its parts with ``jax.named_scope`` (``hvdt.attention``,
``hvdt.mlp``, ``hvdt.loss`` in ``models/transformer.py``;
``hvdt.exchange`` and ``hvdt.optimizer`` in ``optimizer.py``;
``hvdt.kernel.<kernel>`` around every ``pallas_call``), and JAX names
differentiation and rematerialisation itself (``jvp(..)``,
``transpose(jvp(..))``, ``rematted_computation``).  Both end up in the
``op_name`` of each instruction's ``metadata`` in the compiled step's HLO
text, and a trace event carries its instruction's name
(``trace_reduce.Op.name``): that join is all there is here.  No JAX import.

Rules, settled on the full-size v5e compiles of the four cells (PR 24):

* a fusion takes the ``op_name`` of the matmul (``convolution``) it
  fuses; where it fuses none, one of the commonest :func:`phase` (then
  the commonest scopes) among its fused instructions; its own only where
  nothing inside is named.  The fusion's own name is its root's, and the
  root misleads: XLA fuses the caller's ``optax.apply_updates`` add into
  the Adam update, and the scan's ``dynamic_update_slice`` onto a
  weight-gradient matmul;
* any other instruction's ``op_name`` is its own.  Where it has none and
  calls a computation (``while``, ``call``, ``conditional``), it takes
  the same from what it calls, followed through nested calls.  Where it
  has none and calls nothing (the copies, slices and ``copy-start`` /
  ``copy-done`` pairs the compiler inserts), it takes the ``op_name`` of
  its first operand that has one, else the commonest of the loop body it
  sits in; in the entry computation it stays nameless and falls to
  ``unscoped``.  A parameter's ``op_name`` is an argument's name
  (``params['embed']``), not a path, and is never used;
* :func:`phase`, first match wins: ``exchange`` (a collective, or
  ``hvdt.exchange`` on the path) > ``optimizer`` (``hvdt.optimizer``) >
  ``remat`` (``rematted_computation``) > ``backward`` (``transpose(``) >
  ``forward`` (``jvp(``, or a model scope reached by neither wrapper: the
  masks and tables built once outside differentiation) > ``unscoped``.
  The recompute sits *under* ``transpose(jvp())/../checkpoint/``, so only
  ``rematted_computation`` tells it from the backward;
* a scope is on the path where it is a whole segment, bare
  (``/hvdt.attention/``) or inside a wrapper (``jvp(hvdt.loss)``).
"""

from __future__ import annotations

import collections
import functools
import re
from typing import Dict, List, Optional, Tuple

from benchmark import trace_reduce
from benchmark.layer_metrics import devices, steps_on

PHASES = ("forward", "remat", "backward", "optimizer", "exchange",
          "unscoped")
SCOPE_PREFIX = "hvdt."
MODEL_SCOPES = ("hvdt.attention", "hvdt.mlp", "hvdt.loss")

_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLED = re.compile(
    r"(?:calls|body|to_apply|true_computation|false_computation)="
    r"%?([\w.\-]+)|branch_computations=\{([^}]*)\}")
_OPERANDS = re.compile(r"%([\w.\-]+)")


def has_scope(op_name: str, scope: str) -> bool:
    """``scope`` is a whole segment of the path ``op_name``: between
    ``/``, or between a wrapper's parentheses."""
    return re.search(rf"(?:^|[/(]){re.escape(scope)}(?:$|[/)])",
                     op_name) is not None


def phase(op: trace_reduce.Op, op_name: str) -> str:
    return ("exchange" if trace_reduce.is_collective(op)
            else _phase_of_name(op_name))


def _phase_of_name(op_name: str) -> str:
    if has_scope(op_name, "hvdt.exchange"):
        return "exchange"
    if has_scope(op_name, "hvdt.optimizer"):
        return "optimizer"
    if "rematted_computation" in op_name:
        return "remat"
    if "transpose(" in op_name:
        return "backward"
    if "jvp(" in op_name or any(has_scope(op_name, s)
                                for s in MODEL_SCOPES):
        return "forward"
    return "unscoped"


def _scopes(op_name: str) -> tuple:
    return tuple(re.findall(r"hvdt\.[\w.]+", op_name))


def _commonest(names: List[str]) -> str:
    """Of ``names``, the first that has the commonest phase and, among
    those, the commonest scopes; "" for none."""
    if not names:
        return ""
    count = collections.Counter
    best = count(map(_phase_of_name, names)).most_common(1)[0][0]
    names = [n for n in names if _phase_of_name(n) == best]
    scopes = count(map(_scopes, names)).most_common(1)[0][0]
    return next(n for n in names if _scopes(n) == scopes)


@functools.lru_cache(maxsize=1)         # one step program, nine readers
def op_names(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` for every instruction of every
    computation of an HLO module's text, by the rules in the module's
    docstring."""
    own: Dict[str, str] = {}            # instruction -> its own op_name
    opcodes: Dict[str, str] = {}
    called: Dict[str, List[str]] = {}   # instruction -> computations
    operands: Dict[str, List[str]] = {}
    home: Dict[str, str] = {}           # instruction -> its computation
    members: Dict[str, List[str]] = collections.defaultdict(list)
    computation = entry = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            head = line.split("(", 1)[0].split()
            computation = head[-1].lstrip("%") if line.rstrip().endswith(
                "{") and head else None
            if line.startswith("ENTRY"):
                entry = computation
            continue
        text = line.strip().removeprefix("ROOT ")
        name, opcode, _ = trace_reduce.parse_instruction(text)
        if not opcode or computation is None:
            continue
        home[name] = computation
        # A parameter's op_name is the argument's name, not a path.
        m = opcode != "parameter" and _OP_NAME.search(text)
        own[name] = m.group(1) if m else ""
        opcodes[name] = opcode
        members[computation].append(name)
        called[name] = [
            c.strip().lstrip("%") for single, many in _CALLED.findall(text)
            for c in ([single] if single else many.split(","))]
        args = text.split(f" {opcode}(", 1)[1].split(")", 1)[0]
        operands[name] = _OPERANDS.findall(args)

    inside: Dict[str, List[str]] = {}   # computation -> op_names within

    def names_inside(comp: str) -> List[str]:
        if comp not in inside:
            inside[comp] = []           # a cycle finds nothing more
            insts = members.get(comp, ())
            matmuls = [i for i in insts if opcodes[i] == "convolution"
                       and own[i]]
            inside[comp] = [own[i] for i in matmuls] or [
                n for i in insts for n in (
                    [own[i]] if own[i] else
                    [n for c in called[i] for n in names_inside(c)])]
        return inside[comp]

    out: Dict[str, str] = {}
    for name, op_name in own.items():
        if called[name] and (opcodes[name] == "fusion" or not op_name):
            op_name = _commonest([n for c in called[name]
                                  for n in names_inside(c)]) or op_name
        out[name] = op_name
    for name, op_name in out.items():
        if not op_name:
            out[name] = next(
                (out[o] for o in operands[name] if out.get(o)),
                "" if home[name] == entry
                else _commonest(names_inside(home[name])))
    return out


def scoped(names: Dict[str, str]) -> bool:
    """Whether the program the names come from carries the scopes at all.
    A step program loaded from a compilation cache that predates them
    (the cache key ignores metadata) does not, and must not be read as one
    long ``unscoped`` phase."""
    return any(SCOPE_PREFIX in n for n in names.values())


def split(dev: trace_reduce.DeviceTrace, names: Dict[str, str]
          ) -> Dict[str, float]:
    """``{phase: milliseconds per step}`` of the leaf events of one
    device; every phase is there, so the values sum to the leaf time."""
    out = dict.fromkeys(PHASES, 0.0)
    for op in dev.leaves:
        out[phase(op, names.get(op.name, ""))] += op.seconds
    steps = steps_on(dev)
    return {which: 1e3 * seconds / steps for which, seconds in out.items()}


def scope_per_step(dev: trace_reduce.DeviceTrace, names: Dict[str, str],
                   scope: str, pick=lambda op: True
                   ) -> Tuple[Optional[float], Optional[float]]:
    """(milliseconds, events) per step of the leaf events under ``scope``
    that ``pick`` accepts, in whatever phase; (None, None) where there
    are none."""
    picked = [op.seconds for op in dev.leaves
              if pick(op) and has_scope(names.get(op.name, ""), scope)]
    if not picked:
        return None, None
    return 1e3 * sum(picked) / steps_on(dev), len(picked) / steps_on(dev)


# ---------------------------------------------------------------------------
# What the readers under layer_metrics/ share.
# ---------------------------------------------------------------------------


def _slowest(ctx):
    """(the traced device whose step program takes longest, the names of
    the step's instructions); (None, None) without a trace, without a
    step on it, or with a step program that carries no scope."""
    def step_seconds(dev):
        return sum(m.seconds for m in trace_reduce.step_modules(dev)
                   ) / steps_on(dev)

    stepping = [d for d in devices(ctx) if steps_on(d)]
    names = op_names(ctx.hlo_text) if stepping else {}
    if not scoped(names):
        return None, None
    return max(stepping, key=step_seconds), names


def phase_ms(ctx, which: str) -> Optional[float]:
    """Per-layer metric: milliseconds per step in phase ``which`` on the
    device where the step is slowest (one device for all six, so that
    they sum to its leaf time)."""
    dev, names = _slowest(ctx)
    return split(dev, names)[which] if dev else None


def scope_calls(ctx, scope: str, pick=lambda op: True
                ) -> Tuple[Optional[float], Optional[float]]:
    """(milliseconds, events) per step under ``scope`` on that same
    device: what ``layer_metrics.per_step`` gives for a predicate, for a
    name the program gave."""
    dev, names = _slowest(ctx)
    return scope_per_step(dev, names, scope, pick) if dev else (None, None)


def scope_metric(ctx, scope: str) -> Optional[float]:
    """Per-layer metric: milliseconds per step under ``scope`` on that
    same device."""
    return scope_calls(ctx, scope)[0]
