"""From a profiler trace to numbers.  The reduction the benchmark's
per-layer metrics and ``device.busy_s`` rest on, kept here so that no PR
that claims a gain can change it.

What is read from the ``.xplane.pb`` that ``jax.profiler`` writes (looked
at by hand on the v5e, PR 22; PERF.md section 3 says the same):

* planes named ``/device:TPU:<n>`` are the chips.  On each, the line
  ``XLA Modules`` holds one event per executed program (the step program
  is the one that takes most of the time), the line ``XLA Ops`` one event
  per executed HLO instruction and the line ``Async XLA Ops`` one event
  per asynchronous operation, as long as it is in flight.  An event of the
  op lines is named by the instruction's whole text (``%fusion.12 =
  bf16[..] fusion(..), kind=kOutput, calls=..``); :func:`parse_instruction`
  takes the name, the opcode and the fusion kind or custom-call target
  from it.  The trace carries no HLO category;
* control flow (``while``, ``conditional``, ``call``) is an event that
  encloses its body's events on the same line.  Sums by instruction count
  *leaf* events only (those enclosing no other);
* an asynchronous collective is in flight for its event on the ``Async XLA
  Ops`` line (where a trace has no such line: from the start of its
  ``-start`` event to the end of its ``-done`` event on ``XLA Ops``).  A
  synchronous one is a single event and is in flight for its duration.
  Collectives are recognised by :data:`COLLECTIVE_PREFIXES`;
* a Mosaic (Pallas) kernel is a ``custom-call`` whose
  ``custom_call_target`` is ``tpu_custom_call``.  Its instruction is named
  after the jaxpr it came from (``closed_call.47``,
  ``rematted_computation.11``), not after the kernel, and
  ``kernel_metadata`` is empty: the trace cannot tell two kernels apart;
* the plane ``/host:CPU`` holds the host threads, on the device planes'
  clock; the benchmark's own ``TraceAnnotation`` spans (names starting
  ``bench.``) are read from it to say what the host was doing in an idle
  gap of the device.

Busy time is the *union* of the op events' intervals, never their sum: a
``while`` encloses its body, and an asynchronous collective overlaps the
compute that hides it.  Enclosing events count as busy (the program is
running), so what is left idle is what the host left idle.

Everything below :func:`load_xplane` works on plain Python objects, so a
small recorded trace in JSON (:func:`trace_from_json`) drives the tests.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import json
import os
import re
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "reduce-scatter",
                       "all-to-all", "collective-permute",
                       "collective-broadcast")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
MOSAIC_TARGET = "tpu_custom_call"
HOST_SPAN_PREFIX = "bench."

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Op:
    """One event: seconds from the start of the trace.  ``category`` is
    the HLO opcode, ``detail`` the fusion kind or custom-call target."""

    name: str
    start: float
    end: float
    category: str = ""
    detail: str = ""

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class DeviceTrace:
    ops: List[Op]               # every event of the XLA Ops line
    modules: List[Op]           # every event of the XLA Modules line
    async_ops: List[Op] = dataclasses.field(default_factory=list)

    @functools.cached_property
    def leaves(self) -> List[Op]:
        """``leaf_ops(self.ops)``, sorted once for every reader."""
        return leaf_ops(self.ops)


@dataclasses.dataclass
class Trace:
    devices: Dict[str, DeviceTrace]
    host_spans: List[Op]


# ---------------------------------------------------------------------------
# Interval arithmetic.
# ---------------------------------------------------------------------------


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """The union of ``intervals`` as sorted, disjoint intervals."""
    out: List[List[float]] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(hi - lo for lo, hi in merge(intervals))


def subtract(a: Iterable[Interval], b: Iterable[Interval]) -> List[Interval]:
    """The part of the union of ``a`` that the union of ``b`` leaves."""
    out = []
    cover = merge(b)
    for lo, hi in merge(a):
        for blo, bhi in cover:
            if bhi <= lo:
                continue
            if blo >= hi:
                break
            if blo > lo:
                out.append((lo, blo))
            lo = max(lo, bhi)
            if lo >= hi:
                break
        if lo < hi:
            out.append((lo, hi))
    return out


def _span(op: Op) -> Interval:
    return (op.start, op.end)


# ---------------------------------------------------------------------------
# What the events are.
# ---------------------------------------------------------------------------


def leaf_ops(ops: Sequence[Op]) -> List[Op]:
    """The events that enclose no other event of the line (control flow
    encloses its body and is dropped; so are events of no duration)."""
    order = sorted((o for o in ops if o.end > o.start),
                   key=lambda o: (o.start, -o.end))
    leaves, stack = [], []       # stack of [op, has_child]
    for op in order:
        while stack and stack[-1][0].end <= op.start:
            done, parent = stack.pop()
            if not parent:
                leaves.append(done)
        if stack and op.end <= stack[-1][0].end:
            stack[-1][1] = True
        stack.append([op, False])
    leaves.extend(op for op, parent in stack if not parent)
    return sorted(leaves, key=lambda o: o.start)


def is_collective(op: Op) -> bool:
    """By opcode, or by name for one wrapped in ``async-start``/``-done``."""
    return op.category.startswith(COLLECTIVE_PREFIXES) or (
        op.category.startswith("async-")
        and op.name.startswith(COLLECTIVE_PREFIXES))


def is_mosaic(op: Op) -> bool:
    return op.category == "custom-call" and op.detail == MOSAIC_TARGET


_ASYNC_RE = re.compile(r"^(.+)-(start|done)((?:\.\d+)*)$")


def collectives_in_flight(dev: DeviceTrace) -> List[Interval]:
    """When a collective is in flight on this device."""
    out = [_span(o) for o in dev.async_ops if is_collective(o)]
    pair = not out                  # no Async line: pair start with done
    starts: Dict[Tuple[str, str], List[Op]] = {}
    for op in sorted(dev.ops, key=lambda o: o.start):
        if not is_collective(op):
            continue
        m = _ASYNC_RE.match(op.name)
        if not m:
            out.append(_span(op))
        elif not pair:
            continue
        elif m.group(2) == "start":
            starts.setdefault((m.group(1), m.group(3)), []).append(op)
        else:
            opened = starts.get((m.group(1), m.group(3)))
            # A done with no start in the window: its own wait still counts.
            out.append((opened.pop(0).start, op.end) if opened
                       else _span(op))
    return out


def busy_seconds(dev: DeviceTrace) -> float:
    """Seconds in which some operation ran on the device."""
    return total(_span(o) for o in dev.ops)


def exposed_collective_seconds(dev: DeviceTrace) -> float:
    """Seconds in which a collective was in flight on the device while no
    compute operation ran there."""
    compute = [_span(o) for o in dev.leaves if not is_collective(o)]
    return total(subtract(collectives_in_flight(dev), compute))


def step_modules(dev: DeviceTrace) -> List[Op]:
    """The executions of the step program: the module name that takes
    most of the device's time."""
    by_name: Dict[str, float] = {}
    for m in dev.modules:
        by_name[m.name] = by_name.get(m.name, 0.0) + m.seconds
    if not by_name:
        return []
    step = max(by_name, key=by_name.get)
    return sorted((m for m in dev.modules if m.name == step),
                  key=lambda m: m.start)


def sum_seconds(dev: DeviceTrace, pick: Callable[[Op], bool]
                ) -> Tuple[float, int]:
    """(seconds, events) of the leaf events ``pick`` accepts."""
    picked = [o for o in dev.leaves if pick(o)]
    return sum(o.seconds for o in picked), len(picked)


def window(trace: Trace) -> Interval:
    """First start to last end of anything on any device."""
    events = [o for d in trace.devices.values() for o in d.ops + d.modules]
    return (min(o.start for o in events), max(o.end for o in events))


_OP_ID = re.compile(r"[.\d]+$")


def top_device_ops(dev: DeviceTrace, n: int = 10,
                   label: Callable[[Op], str] = lambda op: "") -> List[List]:
    """``[[name, seconds], ...]``: leaf events summed by instruction name
    with its numeric suffix dropped, its opcode, fusion kind or custom-call
    target, and whatever ``label`` adds (the harness marks the fusions
    that hold a convolution)."""
    sums: Dict[str, float] = {}
    for op in dev.leaves:
        tags = " ".join(filter(None, (op.category, op.detail, label(op))))
        key = f"{_OP_ID.sub('', op.name) or op.name} [{tags}]"
        sums[key] = sums.get(key, 0.0) + op.seconds
    return [[k, v] for k, v in sorted(sums.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(dev: DeviceTrace, host_spans: Sequence[Op],
              span: Interval, n: int = 10) -> List[List]:
    """``[[what the host was doing, seconds], ...]``: the device's idle
    time inside ``span``, each gap attributed to the benchmark's host span
    that covers most of it (``host:other`` where none does)."""
    gaps = subtract([span], (_span(o) for o in dev.ops))
    sums: Dict[str, float] = {}
    for lo, hi in gaps:
        best, best_overlap = "host:other", 0.0
        for s in host_spans:
            overlap = min(hi, s.end) - max(lo, s.start)
            if overlap > best_overlap:
                best, best_overlap = s.name, overlap
        sums[best] = sums.get(best, 0.0) + (hi - lo)
    return [[k, v] for k, v in sorted(sums.items(),
                                      key=lambda kv: -kv[1])[:n]]


# ---------------------------------------------------------------------------
# Reading a trace.
# ---------------------------------------------------------------------------


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


_INSTRUCTION = re.compile(
    r"^%?(?P<name>\S+) = .*? (?P<opcode>[a-z][a-z0-9-]*)\(")
_DETAIL = re.compile(r'custom_call_target="([^"]*)"|, kind=(k\w+)')


def parse_instruction(text: str) -> Tuple[str, str, str]:
    """(name, opcode, fusion kind or custom-call target) of an HLO
    instruction's text; a text that is no instruction is its own name."""
    m = _INSTRUCTION.match(text)
    if not m:
        return text.lstrip("%"), "", ""
    d = _DETAIL.search(text, m.end())
    return m.group("name"), m.group("opcode"), \
        (d.group(1) or d.group(2)) if d else ""


def _ops(line) -> List[Op]:
    parsed: Dict[str, Tuple[str, str, str]] = {}
    out = []
    for e in line.events:
        if e.name not in parsed:
            parsed[e.name] = parse_instruction(e.name)
        name, opcode, detail = parsed[e.name]
        out.append(Op(name, e.start_ns * 1e-9, e.end_ns * 1e-9, opcode,
                      detail))
    return out


def load_xplane(path: str) -> Trace:
    """The device lines and the benchmark's host spans of one
    ``.xplane.pb``, through ``jax.profiler.ProfileData`` (JAX alone)."""
    from jax.profiler import ProfileData

    devices: Dict[str, DeviceTrace] = {}
    host_spans: List[Op] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            dev = DeviceTrace([], [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops = _ops(line)
                elif line.name == ASYNC_LINE:
                    dev.async_ops = _ops(line)
                elif line.name == MODULES_LINE:
                    dev.modules = [
                        Op(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                        for e in line.events]
            if dev.ops:
                devices[plane.name] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host_spans.extend(
                    Op(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                    for e in line.events
                    if e.name.startswith(HOST_SPAN_PREFIX))
    return Trace(devices, host_spans)


def instructions_holding(hlo_text: str, opcode: str) -> set:
    """Names of the instructions of an HLO module's text that are an
    ``opcode`` or a fusion whose computation holds one.  The trace names
    a fusion, not what is inside it; the compiled step's text does."""
    holding, calls, direct = set(), {}, set()
    computation = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            head = line.split("(", 1)[0].split()
            computation = head[-1].lstrip("%") if line.rstrip().endswith(
                "{") and head else None
            continue
        name, op, _ = parse_instruction(line.strip().removeprefix("ROOT "))
        if op == opcode:
            direct.add(name)
            if computation:
                holding.add(computation)
        elif op == "fusion":
            m = re.search(r"calls=%?([\w.\-]+)", line)
            if m:
                calls[name] = m.group(1)
    return direct | {n for n, c in calls.items() if c in holding}


def trace_to_json(trace: Trace) -> str:
    return json.dumps({
        "devices": {name: {
            "ops": [dataclasses.astuple(o) for o in d.ops],
            "modules": [dataclasses.astuple(o) for o in d.modules],
            "async_ops": [dataclasses.astuple(o) for o in d.async_ops]}
            for name, d in trace.devices.items()},
        "host_spans": [dataclasses.astuple(o) for o in trace.host_spans]})


def trace_from_json(text: str) -> Trace:
    raw = json.loads(text)
    return Trace(
        {name: DeviceTrace([Op(*o) for o in d["ops"]],
                           [Op(*o) for o in d["modules"]],
                           [Op(*o) for o in d.get("async_ops", [])])
         for name, d in raw["devices"].items()},
        [Op(*o) for o in raw["host_spans"]])


def describe_xplane(path: str, events_per_line: int = 4) -> str:
    """Planes, lines, event counts and a few events with their stats: what
    to read before trusting the reduction on a new runtime."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name}: {len(events)} events")
            for e in events[:events_per_line]:
                out.append(f"    {e.name} start={e.start_ns} "
                           f"dur={e.duration_ns} stats={list(e.stats)}")
    return "\n".join(out)
