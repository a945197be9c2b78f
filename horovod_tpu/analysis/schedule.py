"""Static collective-schedule extraction + distributed-correctness
verification.

Every desync the framework can diagnose today is caught at *runtime*:
the flight recorder (telemetry/flight_recorder.py) names the first
divergent seq only after the stall escalator's abort rung fires.  But on
a jaxpr/HLO stack the collective schedule is a **traceable artifact** —
the reference negotiates collective order at runtime precisely because
frameworks could not prove it statically (ref: controller negotiation,
operations.cc RunLoopOnce), while here the whole issue order is sitting
in the jaxpr before a single step runs.  This module extracts it:

* :func:`extract_schedule` traces a step function and walks the jaxpr
  (descending into ``shard_map`` / ``pjit`` / ``cond`` / ``while`` /
  ``scan`` / custom-VJP sub-jaxprs, in equation order — which IS the
  issue order) collecting every collective primitive into an ordered
  :class:`ScheduleFingerprint`: op kind, axis names, dtype, element
  count, bytes, wire, control-flow context, and whether the collective
  sits downstream of an ``optimization_barrier`` pin.

* Verifier passes over the fingerprint assert the contracts the rest of
  the codebase relies on by convention:

  - :func:`verify_bucket_plan_invariance` — the fusion bucket plan is a
    pure function of the leaf sequence and invariant under dtype-order
    interleaving (two ranks flattening the same tree must issue the
    same buckets — the determinism the per-rank seq alignment needs);
  - :func:`verify_flip_compat` — an autotune leg pair declared
    hot-swappable keeps ONE optimizer state treedef and identical
    output avals, so flipping the leg is a re-jit and never a state
    migration (the AutotunedStep contract for all seven dimensions);
  - :func:`verify_post_pin_psum_family` — in a hierarchical-transport
    program every collective issued after a pin barrier is psum-family
    (barriers erase replication tracking; only psum-family terminals
    re-establish it — the PR-8/9 invariance contract
    transport/hierarchy.py documents);
  - :func:`verify_no_data_dependent_collectives` — a collective under
    one branch of ``cond`` or inside ``while`` executes a
    data-dependent number of times: if host data diverges across
    ranks, so does the issue order — the classic mismatched-collective
    hang, flagged before it ever runs.

* The fingerprint exports to JSON (:meth:`ScheduleFingerprint.save`)
  and is cross-checked at **runtime** by the flight recorder:
  ``HVDT_EXPECTED_SCHEDULE`` names the exported file and
  ``emit_desync_report`` then reports static-expected vs
  runtime-observed (:func:`first_schedule_deviation`), not just
  observed-vs-observed.

jax-0.4.37 guard: only ``jax.make_jaxpr`` / ``jax.jit(...).lower`` and
jaxpr-object introspection — no ``jax.typeof`` / ``lax.pcast`` /
``shard_map``-API dependence anywhere here.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import re
from collections import Counter
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

__all__ = [
    "CollectiveEvent", "ScheduleFingerprint", "extract_schedule",
    "hlo_collective_counts", "verify_bucket_plan_invariance",
    "verify_flip_compat", "verify_post_pin_psum_family",
    "verify_no_data_dependent_collectives", "verify_a2a_ppermute_pairing",
    "first_schedule_deviation",
    "load_fingerprint", "COLLECTIVE_PRIMS", "PSUM_FAMILY",
]

FINGERPRINT_VERSION = 1

# jaxpr primitive name -> canonical collective kind (probed on the
# container's jax 0.4.37: lax.psum_scatter traces as `reduce_scatter`).
COLLECTIVE_PRIMS: Dict[str, str] = {
    "psum": "psum",
    "reduce_scatter": "reduce_scatter",
    "psum_scatter": "reduce_scatter",      # newer jax spelling
    "all_gather": "all_gather",
    "all_to_all": "all_to_all",
    "ppermute": "ppermute",
    "pmax": "pmax",
    "pmin": "pmin",
}

# Collectives whose terminal op re-establishes replication over the
# reduce group after an optimization_barrier pin (barriers erase
# replication tracking — transport/hierarchy.py InflightHierarchical).
# The repo's invariant allgather lowers to a psum of a displaced buffer
# (ops/device.invariant_allgather_shards), so it lands in this set
# by construction.
PSUM_FAMILY = frozenset({"psum", "reduce_scatter"})

# Control-flow contexts whose body executes a data-dependent number of
# times (or on a data-dependent branch): a collective under one of
# these is a cross-rank desync hazard.  `scan` is excluded — its trip
# count is a trace-time constant, identical on every rank.
DATA_DEPENDENT_CONTEXTS = frozenset({"cond", "while"})

# fingerprint op kind -> the op name the flight recorder books
# (telemetry feed sites: "allreduce"/"reduce_scatter"/"allgather"/...).
EVENT_OP_NAMES = {
    "psum": "allreduce",
    "reduce_scatter": "reduce_scatter",
    "all_gather": "allgather",
    "all_to_all": "alltoall",
    "ppermute": "ppermute",
    "pmax": "allreduce",
    "pmin": "allreduce",
}


@dataclasses.dataclass(frozen=True)
class CollectiveEvent:
    """One collective in the static schedule, in issue order."""

    index: int                       # position in the schedule
    op: str                          # canonical kind (COLLECTIVE_PRIMS)
    axes: Tuple[str, ...]            # mesh axes reduced/exchanged over
    dtype: str                       # operand dtype name
    count: int                       # operand element count
    nbytes: int                      # operand bytes
    context: Tuple[str, ...]         # enclosing control-flow primitives
    post_barrier: bool               # downstream of optimization_barrier
    # How many optimization_barriers were issued before this collective
    # — the overlap pipeline's bucket slot (events sharing a value were
    # issued in the same flight window).  Metadata like nbytes: the
    # cost model consumes it, the digest does not.
    barriers_before: int = 0

    @property
    def event_op(self) -> str:
        """The op name the flight recorder would book for this entry."""
        return EVENT_OP_NAMES.get(self.op, self.op)

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["axes"] = list(self.axes)
        d["context"] = list(self.context)
        d["event_op"] = self.event_op
        return d


class ScheduleFingerprint:
    """Canonical, ordered collective schedule of one traced program.

    The digest hashes exactly the fields two ranks must agree on for
    their per-rank seq counters to align (op kind, axes, dtype, element
    count, control-flow context) — byte counts and barrier positions
    ride along as metadata but a pure metadata change (e.g. a different
    wire estimate) does not change identity.
    """

    def __init__(self, events: Sequence[CollectiveEvent],
                 n_barriers: int = 0, label: str = ""):
        self.events: List[CollectiveEvent] = list(events)
        self.n_barriers = int(n_barriers)
        self.label = str(label)

    @property
    def digest(self) -> str:
        core = [(e.op, list(e.axes), e.dtype, e.count, list(e.context))
                for e in self.events]
        return hashlib.sha256(
            json.dumps(core, sort_keys=True).encode()).hexdigest()

    def counts(self) -> Counter:
        return Counter(e.op for e in self.events)

    def total_bytes(self) -> int:
        return sum(e.nbytes for e in self.events)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "version": FINGERPRINT_VERSION,
            "label": self.label,
            "digest": self.digest,
            "n_barriers": self.n_barriers,
            "events": [e.to_dict() for e in self.events],
        }

    def to_json(self, indent: Optional[int] = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    def save(self, path: str) -> str:
        with open(path, "w") as fh:
            fh.write(self.to_json())
        return path

    @classmethod
    def from_dict(cls, doc: Dict[str, Any]) -> "ScheduleFingerprint":
        events = [
            CollectiveEvent(
                index=int(e.get("index", i)), op=str(e["op"]),
                axes=tuple(e.get("axes", ())), dtype=str(e.get("dtype", "")),
                count=int(e.get("count", 0)), nbytes=int(e.get("nbytes", 0)),
                context=tuple(e.get("context", ())),
                post_barrier=bool(e.get("post_barrier", False)),
                barriers_before=int(e.get(
                    "barriers_before",
                    1 if e.get("post_barrier") else 0)))
            for i, e in enumerate(doc.get("events", []))]
        return cls(events, n_barriers=int(doc.get("n_barriers", 0)),
                   label=str(doc.get("label", "")))

    def summary(self) -> str:
        c = self.counts()
        ops = " ".join(f"{k}={v}" for k, v in sorted(c.items()))
        return (f"schedule[{self.label or 'step'}]: "
                f"{len(self.events)} collectives ({ops or 'none'}), "
                f"{self.n_barriers} barriers, digest {self.digest[:12]}")


def load_fingerprint(path: str) -> ScheduleFingerprint:
    with open(path) as fh:
        return ScheduleFingerprint.from_dict(json.load(fh))


# ---------------------------------------------------------------------------
# Extraction
# ---------------------------------------------------------------------------


def _axes_of(params: Dict[str, Any]) -> Tuple[str, ...]:
    raw = params.get("axes", params.get("axis_name", ()))
    if raw is None:
        raw = ()
    if isinstance(raw, (str, int)):
        raw = (raw,)
    return tuple(str(a) for a in raw)


def _sub_jaxprs(eqn) -> List[Tuple[str, Any]]:
    """(context_name, jaxpr) pairs for every sub-jaxpr an equation
    carries — cond branches, while cond/body, scan/shard_map/pjit
    bodies, custom-VJP call jaxprs."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    out: List[Tuple[str, Any]] = []
    name = eqn.primitive.name
    for k, v in eqn.params.items():
        vals = v if isinstance(v, (tuple, list)) else [v]
        for sub in vals:
            if isinstance(sub, ClosedJaxpr):
                out.append((name, sub.jaxpr))
            elif isinstance(sub, Jaxpr):
                out.append((name, sub))
    return out


class _Walker:
    def __init__(self) -> None:
        self.events: List[CollectiveEvent] = []
        self.n_barriers = 0

    def walk(self, jaxpr, context: Tuple[str, ...] = ()) -> None:
        import numpy as np

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "optimization_barrier":
                self.n_barriers += 1
                continue
            kind = COLLECTIVE_PRIMS.get(name)
            if kind is not None:
                aval = eqn.invars[0].aval if eqn.invars else None
                shape = tuple(getattr(aval, "shape", ()) or ())
                dtype = getattr(aval, "dtype", None)
                count = int(np.prod(shape)) if shape else 1
                itemsize = np.dtype(dtype).itemsize if dtype is not None \
                    else 0
                self.events.append(CollectiveEvent(
                    index=len(self.events), op=kind,
                    axes=_axes_of(eqn.params),
                    dtype=(np.dtype(dtype).name if dtype is not None
                           else ""),
                    count=count, nbytes=count * itemsize,
                    context=context,
                    post_barrier=self.n_barriers > 0,
                    barriers_before=self.n_barriers))
                continue
            for sub_name, sub in _sub_jaxprs(eqn):
                # Transparent wrappers (pjit, closed_call, remat,
                # custom-AD calls, shard_map) keep the parent context;
                # genuine control flow is recorded by primitive name.
                if sub_name in ("cond", "while", "scan"):
                    self.walk(sub, context + (sub_name,))
                else:
                    self.walk(sub, context)


def extract_schedule(fn: Callable, *args: Any, label: str = "",
                     **kwargs: Any) -> ScheduleFingerprint:
    """Trace ``fn(*args, **kwargs)`` and extract its ordered collective
    schedule.  Pure trace — nothing executes on devices.  Call under
    the same mesh/axis bindings the real step uses (a ``shard_map``-
    wrapping fn binds its own axes and needs no context manager)."""
    import jax

    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    w = _Walker()
    w.walk(jaxpr.jaxpr)
    return ScheduleFingerprint(w.events, n_barriers=w.n_barriers,
                               label=label)


_HLO_COLLECTIVES = re.compile(
    r"\b(all[-_]reduce|reduce[-_]scatter|all[-_]gather|all[-_]to[-_]all|"
    r"collective[-_]permute)\b")


def hlo_collective_counts(fn: Callable, *args: Any,
                          **kwargs: Any) -> Counter:
    """Collective-op histogram of the *lowered* HLO/StableHLO text —
    the cross-check that what the jaxpr schedules is what XLA was
    handed (post-lowering fusion/CSE may legally shrink these counts;
    they must never grow)."""
    import jax

    txt = jax.jit(fn).lower(*args, **kwargs).as_text()
    canon = {"all-reduce": "all_reduce", "reduce-scatter": "reduce_scatter",
             "all-gather": "all_gather", "all-to-all": "all_to_all",
             "collective-permute": "collective_permute"}
    c: Counter = Counter()
    for m in _HLO_COLLECTIVES.finditer(txt):
        tok = m.group(1).replace("-", "_")
        c[canon.get(tok, tok)] += 1
    return c


# ---------------------------------------------------------------------------
# Verifier passes.  Each returns a list of finding dicts; empty = pass.
# ---------------------------------------------------------------------------


def _finding(check: str, message: str, **extra: Any) -> Dict[str, Any]:
    d = {"check": check, "message": message}
    d.update(extra)
    return d


def verify_no_data_dependent_collectives(
        fp: ScheduleFingerprint) -> List[Dict[str, Any]]:
    """Flag collectives under ``cond``/``while``: their issue count is
    data-dependent, so host-data divergence across ranks becomes a
    mismatched-collective hang (the desync class PR 6's forensics can
    only diagnose after the fact — this names it before it runs)."""
    out = []
    for e in fp.events:
        bad = [c for c in e.context if c in DATA_DEPENDENT_CONTEXTS]
        if bad:
            out.append(_finding(
                "data-dependent-collective",
                f"collective #{e.index} ({e.op} over {list(e.axes)}) is "
                f"issued under data-dependent control flow "
                f"{'/'.join(bad)} — a cross-rank desync hazard; hoist "
                f"the collective out of the branch or make the "
                f"predicate replicated-by-construction",
                event=e.to_dict()))
    return out


def verify_post_pin_psum_family(
        fp: ScheduleFingerprint) -> List[Dict[str, Any]]:
    """For hierarchical-transport programs: every collective issued
    after an ``optimization_barrier`` pin must be psum-family, because
    the pin erases replication tracking and only psum-family terminals
    re-establish it (the transport/hierarchy.py invariance contract)."""
    out = []
    for e in fp.events:
        if e.post_barrier and e.op not in PSUM_FAMILY:
            out.append(_finding(
                "post-pin-collective",
                f"collective #{e.index} ({e.op} over {list(e.axes)}) is "
                f"issued after a pin barrier but is not psum-family "
                f"({sorted(PSUM_FAMILY)}) — it cannot re-establish "
                f"replication over the reduce group",
                event=e.to_dict()))
    return out


def verify_bucket_plan_invariance(
        leaves: Sequence[Any],
        threshold_bytes: Optional[int] = None) -> List[Dict[str, Any]]:
    """The fusion bucket plan must be a pure function of the leaf
    sequence: repeat-stable, and invariant under dtype-order
    *interleaving* (the planner groups by canonical dtype name, so
    which dtype happens to appear first must not change the plan).
    Two ranks flattening the same pytree rely on exactly this to issue
    identical buckets."""
    from ..ops import device as dev
    from ..ops.overlap import overlap_schedule

    leaves = list(leaves)
    if not leaves:
        return []
    t = dev._validated_threshold(threshold_bytes)
    out = []

    plan_a = dev.fused_allreduce_buckets(leaves, t)
    plan_b = dev.fused_allreduce_buckets(leaves, t)
    if plan_a != plan_b:
        out.append(_finding(
            "bucket-plan-unstable",
            "fused_allreduce_buckets returned different plans for the "
            "same leaf sequence — nondeterministic planning breaks "
            "cross-rank seq alignment"))

    # Interleave dtypes differently while preserving within-dtype
    # order (the planner's documented equivalence class): round-robin
    # across the dtype groups instead of the original interleaving.
    import numpy as np

    groups: Dict[str, List[int]] = {}
    for i, leaf in enumerate(leaves):
        groups.setdefault(np.dtype(
            getattr(leaf, "dtype", np.float32)).name, []).append(i)
    if len(groups) > 1:
        queues = [list(v) for _, v in sorted(groups.items())]
        perm: List[int] = []
        while any(queues):
            for q in queues:
                if q:
                    perm.append(q.pop(0))
        permuted = [leaves[i] for i in perm]
        plan_p = dev.fused_allreduce_buckets(permuted, t)
        # Map the permuted plan back to original indices; bucket
        # composition must be identical.
        mapped = sorted(tuple(sorted(perm[i] for i in b)) for b in plan_p)
        orig = sorted(tuple(sorted(b)) for b in plan_a)
        if mapped != orig:
            out.append(_finding(
                "bucket-plan-permutation",
                "bucket plan changed under dtype-order interleaving of "
                "the same leaves — the plan depends on encounter order, "
                "not canonical dtype order"))

    # The overlap plan must stay the documented reversal of the fused
    # plan (bucket 0 = the leaves whose grads exist first).
    n = len(leaves)
    rev = dev.fused_allreduce_buckets(list(reversed(leaves)), t)
    expect = [[n - 1 - i for i in b] for b in rev]
    if overlap_schedule(leaves, t) != expect:
        out.append(_finding(
            "overlap-plan-drift",
            "overlap_schedule no longer equals the reverse-topological "
            "mapping of fused_allreduce_buckets — the issue order the "
            "barrier chain pins has drifted from the plan"))
    return out


def verify_a2a_ppermute_pairing(
        fp: ScheduleFingerprint) -> List[Dict[str, Any]]:
    """The 4D-schedule closure checks.

    * **a2a pairing** — MoE combine reverses dispatch, so every
      ``all_to_all`` signature (axes, dtype, element count) must appear
      an EVEN number of times per control-flow context: an odd count
      means tokens were scattered onto the expert axis and never
      gathered back (or a combine exchanges a different payload than
      its dispatch — either way the expert-parallel layout leaks out of
      the MoE block).  The int8 dispatch wire issues two a2a per leg
      (payload + scales); each signature still pairs across
      dispatch/combine, so the parity check holds for every wire.
    * **ppermute clocking** — every ``ppermute`` must sit under a
      ``scan`` context: the 1F1B microbatch clock is a ``lax.scan``,
      and a hand-rolled ppermute outside it runs outside the
      warmup/steady/cooldown accounting, so its ticks are invisible to
      the bubble-fraction telemetry the cost model is validated
      against."""
    out = []
    a2a: Dict[Tuple, List[CollectiveEvent]] = {}
    for e in fp.events:
        if e.op == "all_to_all":
            key = (e.axes, e.dtype, e.count, e.context)
            a2a.setdefault(key, []).append(e)
        elif e.op == "ppermute" and "scan" not in e.context:
            out.append(_finding(
                "ppermute-outside-scan",
                f"collective #{e.index} (ppermute over {list(e.axes)}) "
                f"is issued outside a scan body — it runs outside the "
                f"1F1B microbatch clock, so its ticks escape the "
                f"warmup/steady/cooldown phase accounting",
                event=e.to_dict()))
    for key, evs in sorted(a2a.items()):
        if len(evs) % 2 != 0:
            axes, dtype, count, _ = key
            out.append(_finding(
                "unpaired-all-to-all",
                f"all_to_all signature (axes={list(axes)}, dtype={dtype}, "
                f"count={count}) appears {len(evs)} time(s) — "
                f"dispatch/combine must pair, an odd count means the "
                f"expert-parallel layout leaks out of the MoE block",
                indices=[e.index for e in evs]))
    return out


def verify_flip_compat(step_a: Callable, step_b: Callable,
                       args: Sequence[Any], *,
                       state_a: Any = None, state_b: Any = None,
                       dim: str = "") -> Dict[str, Any]:
    """Verify an autotune leg pair is hot-swap compatible: identical
    optimizer-state treedefs (the one-state-tree contract every
    ``HVDT_AUTOTUNE_*`` dimension declares) and identical output avals,
    so the flip is a re-jit — a *schedule* delta only, never a state
    migration or a recompile-unsafe signature change.

    Returns ``{"compatible", "findings", "delta", "digest_a",
    "digest_b"}`` where ``delta`` is the per-op schedule count
    difference between the legs (legs legitimately lower differently —
    that is the point of the dimension)."""
    import jax

    findings: List[Dict[str, Any]] = []
    label = dim or "leg"
    if (state_a is None) != (state_b is None):
        findings.append(_finding(
            "flip-state-treedef",
            f"{label}: one leg produced optimizer state and the other "
            f"did not"))
    elif state_a is not None:
        td_a = jax.tree.structure(state_a)
        td_b = jax.tree.structure(state_b)
        if td_a != td_b:
            findings.append(_finding(
                "flip-state-treedef",
                f"{label}: optimizer state treedefs differ between legs "
                f"({td_a} vs {td_b}) — flipping mid-run would be a "
                f"state migration, not a re-jit"))
        else:
            shapes_a = [(getattr(l, "shape", None),
                         str(getattr(l, "dtype", "")))
                        for l in jax.tree.leaves(state_a)]
            shapes_b = [(getattr(l, "shape", None),
                         str(getattr(l, "dtype", "")))
                        for l in jax.tree.leaves(state_b)]
            if shapes_a != shapes_b:
                findings.append(_finding(
                    "flip-state-shapes",
                    f"{label}: optimizer state leaf shapes/dtypes differ "
                    f"between legs"))

    jaxpr_a = jax.make_jaxpr(step_a)(*args)
    jaxpr_b = jax.make_jaxpr(step_b)(*args)
    out_a = [(tuple(getattr(v, "shape", ())), str(getattr(v, "dtype", "")))
             for v in jaxpr_a.out_avals]
    out_b = [(tuple(getattr(v, "shape", ())), str(getattr(v, "dtype", "")))
             for v in jaxpr_b.out_avals]
    if out_a != out_b:
        findings.append(_finding(
            "flip-output-avals",
            f"{label}: step output avals differ between legs "
            f"({out_a} vs {out_b}) — the caller's downstream program "
            f"would need recompilation beyond the step itself"))

    wa, wb = _Walker(), _Walker()
    wa.walk(jaxpr_a.jaxpr)
    wb.walk(jaxpr_b.jaxpr)
    fp_a = ScheduleFingerprint(wa.events, wa.n_barriers, f"{label}:a")
    fp_b = ScheduleFingerprint(wb.events, wb.n_barriers, f"{label}:b")
    delta = dict(Counter(fp_b.counts()) - Counter(fp_a.counts()))
    delta.update({f"-{k}": v for k, v in
                  (Counter(fp_a.counts()) - Counter(fp_b.counts())).items()})
    return {
        "compatible": not findings,
        "findings": findings,
        "delta": delta,
        "digest_a": fp_a.digest,
        "digest_b": fp_b.digest,
    }


# ---------------------------------------------------------------------------
# Runtime cross-check: static-expected vs flight-recorder-observed
# ---------------------------------------------------------------------------


def first_schedule_deviation(
        events: Sequence[Dict[str, Any]],
        expected: Sequence[Dict[str, Any]],
        cyclic: bool = True) -> Optional[Dict[str, Any]]:
    """First flight-recorder event that disagrees with the static
    schedule, or None when every observed event matches.

    ``events`` are flight-recorder dicts (seq/op/dtype/...);
    ``expected`` are fingerprint event dicts.  The static schedule is
    one *step*; a run's seq stream repeats it, so matching is cyclic by
    seq (seq k matches expected entry ``(k-1) % len(expected)``).
    Op names compare via the recorder vocabulary (``event_op``); dtype
    compares only when both sides carry one."""
    if not expected:
        return None
    n = len(expected)
    for ev in sorted(events, key=lambda e: int(e.get("seq", 0))):
        seq = int(ev.get("seq", 0))
        idx = (seq - 1) % n if cyclic else seq - 1
        if idx < 0 or idx >= n:
            continue
        exp = expected[idx]
        exp_op = exp.get("event_op") or EVENT_OP_NAMES.get(
            str(exp.get("op", "")), str(exp.get("op", "")))
        obs_op = str(ev.get("op", "")).lower()
        mismatch = None
        if obs_op and exp_op and obs_op != exp_op:
            mismatch = f"op {obs_op!r} != expected {exp_op!r}"
        else:
            exp_dt = str(exp.get("dtype", ""))
            obs_dt = str(ev.get("dtype", ""))
            if exp_dt and obs_dt and exp_dt != obs_dt:
                mismatch = f"dtype {obs_dt!r} != expected {exp_dt!r}"
        if mismatch:
            return {
                "seq": seq,
                "reason": mismatch,
                "expected": dict(exp),
                "observed": {k: ev.get(k) for k in
                             ("op", "name", "dtype", "shape", "nbytes")},
            }
    return None
