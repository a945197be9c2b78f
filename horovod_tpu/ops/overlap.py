"""Overlap scheduling layer — dependency-ordered, latency-hidden gradient
exchange.

The core insight of the source paper (Horovod: tensor fusion + overlapping
allreduce with the backward pass) and of "Exploring the limits of
Concurrency in ML Training on Google TPUs" (latency-hiding collectives
behind compute is what separates 0.3-MFU from 0.5-MFU runs) applied to the
jit data plane.  ``fused_allreduce`` packs buckets well, but a
compute-then-communicate step only starts collectives after the whole
backward has materialized.  This module turns the train step into a
pipelined exchange:

* **Reverse-topological bucket schedule** — gradient leaves arrive in
  forward (parameter) order and the backward materializes them in
  *reverse*, so buckets are planned over the reversed leaf order
  (:func:`overlap_schedule`, reusing ``fused_allreduce_buckets``) and each
  bucket's fused allreduce is issued as soon as that segment's grads
  exist.  Issue order is pinned with ``jax.lax.optimization_barrier`` — a
  token chain threads every bucket's *payload* (never its result, which
  would serialize done→issue and kill the overlap) so XLA cannot
  re-serialize the collectives into one trailing block.

* **Segmented VJP** (:func:`overlap_value_and_grad`) — for models
  expressed as a chain of stages, the backward is walked stage by stage
  and each stage's exchange is issued *between* VJP segments: the
  upstream cotangent is barriered with the stage's payload token, so the
  traced program literally interleaves collectives with backward compute
  (the lowered-HLO contract tests/test_overlap.py pins).

* **Pipelined int8 wire** — the quantized collective
  (quant/collectives.py) is split into ``start`` (quantize + wire-format
  reduce-scatter) and ``finish`` (dequant-accumulate + requantize +
  reassembly); the scheduler issues bucket N+1's wire hop before
  finishing bucket N, so N's dequant-accumulate overlaps N+1's wire
  phase.

* **Pallas latency-hiding leg** (:func:`exchange_and_update`,
  :func:`pipelined_sgd`) — the single-HBM-pass optimizer update
  (ops/optim_kernels.py) of bucket N runs while bucket N+1's collective
  is in flight, so the optimizer is no longer a serial epilogue.

* **Async collective flags** (:func:`enable_latency_hiding`) — engages
  XLA:TPU's latency-hiding scheduler / async collective fusion through
  the ``LIBTPU_INIT_ARGS`` env contract (``HVDT_XLA_LATENCY_HIDING``),
  which is what actually turns the dependency freedom above into
  overlapped execution on hardware.

Zero-overhead contract (same pattern as telemetry/instrument.py and
resilience/faults.py): with ``HVDT_OVERLAP`` unset/off,
:func:`get_scheduler` returns ``None`` and :func:`exchange_fn` returns
``ops.device.fused_allreduce`` ITSELF — the exact pre-existing code
object, identity-tested — so the monolithic path stays byte-for-byte the
``HVDT_OVERLAP=off`` fallback.

Numerics: bucketing and barriers never change f32 math — a psum is
elementwise across ranks, so any bucketing slices out bitwise-identical
leaves (tests pin grads AND updated params bitwise against the
monolithic path on a mesh-8 CPU run).  The int8 wire keeps the
established block-scale/2 error bound per stage; bucket *composition*
differs from the forward plan, so int8 results are bounded, not bitwise.

jax-0.4.37 guard: everything here uses ``lax.optimization_barrier``
(present since 0.4.x) and the env-contract flags — no ``jax.typeof`` /
``lax.pcast`` / ``shard_map``-API dependence anywhere on this path.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..common import config
from ..common.logging_util import get_logger
from ..common.types import ReduceOp
from . import device as dev

__all__ = [
    "enabled", "get_scheduler", "exchange_fn", "reset", "OverlapScheduler",
    "overlap_schedule", "overlap_value_and_grad", "exchange_and_update",
    "pipelined_sgd", "enable_latency_hiding", "overlap_fraction",
    "last_schedule", "reset_accounting",
]

log = get_logger(__name__)

_TRUTHY = ("1", "true", "yes", "on")


def enabled() -> bool:
    """Whether the overlap scheduling layer is on (``HVDT_OVERLAP``)."""
    return os.environ.get("HVDT_OVERLAP", "").strip().lower() in _TRUTHY


# ---------------------------------------------------------------------------
# Process-wide scheduler (env-gated, cached on the raw env string so per-test
# monkeypatching rebuilds it — same idiom as telemetry.instrument.get_recorder)
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_cached_env: Optional[str] = "\0unset"   # sentinel != any real env value
_cached_scheduler: Optional["OverlapScheduler"] = None


def get_scheduler() -> Optional["OverlapScheduler"]:
    """The process-wide overlap scheduler, or ``None`` when off.

    The disabled steady state costs one environ read and a string
    compare; call sites branch on ``is None`` and touch nothing else."""
    global _cached_env, _cached_scheduler
    raw = os.environ.get("HVDT_OVERLAP")
    if raw != _cached_env:
        with _lock:
            if raw != _cached_env:
                _cached_scheduler = OverlapScheduler() if enabled() else None
                _cached_env = raw
    return _cached_scheduler


def exchange_fn() -> Callable:
    """The bucketed gradient-exchange callable the optimizer layer uses.

    ``HVDT_OVERLAP`` on → the scheduler's dependency-ordered
    :meth:`OverlapScheduler.exchange`; off/unset → the monolithic
    ``ops.device.fused_allreduce`` — the EXACT pre-existing code object
    (``exchange_fn() is fused_allreduce``, identity-tested), so the off
    path carries zero wrapper objects."""
    sched = get_scheduler()
    return dev.fused_allreduce if sched is None else sched.exchange


def reset() -> None:
    """Drop the cached scheduler (test isolation)."""
    global _cached_env, _cached_scheduler
    with _lock:
        _cached_env = "\0unset"
        _cached_scheduler = None


# ---------------------------------------------------------------------------
# Overlap accounting: collective bytes issued with compute left to hide
# under vs. total — the trace-time feed for the hvdt_overlap_fraction
# gauge.  Recorded at TRACE time (under jit
# the compiled program, not this host code, runs the schedule), same
# path=jit convention as the per-collective instrumentation.
# ---------------------------------------------------------------------------

_acct_lock = threading.Lock()
_acct_hidden = 0.0
_acct_total = 0.0
_last_schedule: Optional[dict] = None


def _account(bucket_bytes: List[int], wire: str) -> None:
    global _acct_hidden, _acct_total, _last_schedule
    total = float(sum(bucket_bytes))
    # Every bucket except the LAST issued still has backward compute (or
    # pipelined updates) scheduled under its flight window; the final
    # collective has nothing left to hide under.
    hidden = float(sum(bucket_bytes[:-1])) if len(bucket_bytes) > 1 else 0.0
    with _acct_lock:
        _acct_hidden += hidden
        _acct_total += total
        _last_schedule = {
            "buckets": len(bucket_bytes),
            "bucket_bytes": list(bucket_bytes),
            "hidden_buckets": max(0, len(bucket_bytes) - 1),
            "wire": wire,
        }
    from ..telemetry import instrument as _ti

    rec = _ti.get_recorder()
    if rec is not None:
        rec.observe_overlap(hidden, total)


def overlap_fraction() -> Optional[float]:
    """Collective bytes issued with compute left to hide under ÷ total
    collective bytes, cumulative over every schedule traced in this
    process (the byte-weighted proxy for collective-seconds hidden ÷
    total collective seconds until a TPU profile refines it).  ``None``
    before any overlapped exchange has been traced."""
    with _acct_lock:
        if _acct_total <= 0:
            return None
        return _acct_hidden / _acct_total


def last_schedule() -> Optional[dict]:
    """Bucket plan of the most recently traced overlapped exchange."""
    with _acct_lock:
        return dict(_last_schedule) if _last_schedule else None


def reset_accounting() -> None:
    global _acct_hidden, _acct_total, _last_schedule
    with _acct_lock:
        _acct_hidden = _acct_total = 0.0
        _last_schedule = None


# ---------------------------------------------------------------------------
# Schedule planning
# ---------------------------------------------------------------------------


def overlap_schedule(leaves: Sequence[Any],
                     threshold_bytes: Optional[int] = None
                     ) -> List[List[int]]:
    """Reverse-topological bucket plan over a gradient pytree's leaves.

    Gradient leaves arrive in forward (parameter) order; the backward
    materializes them in reverse, so the plan is
    ``fused_allreduce_buckets`` over the REVERSED leaf order mapped back
    to original indices — bucket 0 holds the output-side leaves whose
    grads exist first, and is issued first.  Pure planning function;
    host-side, shape-only."""
    threshold_bytes = dev._validated_threshold(threshold_bytes)
    n = len(leaves)
    rev = list(reversed(list(leaves)))
    return [[n - 1 - i for i in b]
            for b in dev.fused_allreduce_buckets(rev, threshold_bytes)]


def _payload_token(flat):
    """A tiny (1-element) slice of a bucket payload — the dependency
    handle the barrier chain threads.  Depends only on the payload, so
    pinning on it never waits for the collective's *result*."""
    return lax.slice_in_dim(flat, 0, 1)


def _exchange_leaves(leaves, axis, op, threshold_bytes, prescale_factor,
                     postscale_factor, wire_dtype, quant_wire, token,
                     leaf_finish=None):
    """Core dependency-ordered exchange over a flat leaf list.

    ``quant_wire`` names the quantized leg ("int8" / "int4") or is
    falsy for exact/cast wires (a bare ``True`` means int8, the legacy
    bool spelling).

    Returns ``(cells, token)`` where ``cells[i]`` is the reduced leaf
    (or whatever ``leaf_finish(i, reduced_leaf, pin)`` returned) and
    ``token`` is the last bucket's payload token — thread it into the
    next call (the segmented backward) to keep one global issue order.

    Two-phase walk:

    1. **issue** — every bucket's payload is concatenated, barriered
       with the previous payload's token (issue-order pin) and its
       collective started (for the int8 wire: the quantize + wire-format
       reduce-scatter ``quantized_allreduce_start``; under a transport
       policy: the hierarchical fast-axis reduce-scatter + slow-axis
       wire hop ``hierarchical_allreduce_start``);
    2. **finish** — bucket k's epilogue (dequant-accumulate for the
       quantized wire, slow finish + allgather for the hierarchical
       path, the optimizer update when ``leaf_finish`` runs one) is
       barriered with bucket k+1's payload, so it is scheduled while
       k+1's collective is in flight.
    """
    schedule = overlap_schedule(leaves, threshold_bytes)

    from ..telemetry import instrument as _ti
    from ..transport import policy as _tpolicy

    quant_leg = "int8" if quant_wire is True else (quant_wire or None)

    rec = _ti.get_recorder()
    _res = _tpolicy.resolve_axis(axis)
    hier = (_res is not None and _res.kind == "hierarchical"
            and op in (ReduceOp.SUM, ReduceOp.AVERAGE))
    _axis_label = "+".join((axis,) if isinstance(axis, str)
                           else tuple(axis))

    issued = []   # (bucket, shapes, sizes, orig_dtype, kind, state, payload)
    bucket_bytes: List[int] = []
    for bi, bucket in enumerate(schedule):
        parts = [leaves[i] for i in bucket]
        shapes = [p.shape for p in parts]
        sizes = [p.size for p in parts]
        flat = jnp.concatenate([jnp.ravel(p) for p in parts]) \
            if len(parts) > 1 else jnp.ravel(parts[0])
        orig_dtype = flat.dtype
        float_bucket = jnp.issubdtype(orig_dtype, jnp.floating)
        hier_bucket = hier and float_bucket
        if wire_dtype is not None and flat.dtype != wire_dtype \
                and not hier_bucket:
            flat = flat.astype(wire_dtype)
        # Issue-order pin: this payload cannot be scheduled before the
        # previous bucket's payload, so collectives keep the
        # reverse-topological order instead of being re-serialized.
        if token is not None:
            flat, _ = lax.optimization_barrier((flat, token))
        token = _payload_token(flat)
        nbytes = int(flat.size) * jnp.dtype(flat.dtype).itemsize
        quant_bucket = (quant_leg is not None and float_bucket
                        and not hier_bucket)
        if hier_bucket:
            from ..transport import hierarchy as _th

            bucket_bytes.append(_th.wire_bytes_estimate(
                _res, int(flat.size),
                jnp.dtype(flat.dtype).itemsize) or nbytes)
        elif quant_bucket:
            from ..quant import kernels as _qk

            _wb = (_qk.wire_bytes_int4 if quant_leg == "int4"
                   else _qk.wire_bytes)
            bucket_bytes.append(int(_wb(
                int(flat.size), _qk.quant_block_size())))
        else:
            bucket_bytes.append(nbytes)
        if rec is not None:
            rec.observe_fusion_fill(nbytes / float(threshold_bytes))
            if not quant_bucket and not hier_bucket:
                rec.record_collective(
                    "allreduce", jnp.dtype(orig_dtype).name,
                    jnp.dtype(flat.dtype).name, nbytes,
                    count=len(parts), path="jit", axis=_axis_label)
        with jax.named_scope(f"hvdt.overlap.b{bi}"):
            if hier_bucket:
                from ..transport import hierarchy as _th

                state = _th.hierarchical_allreduce_start(
                    flat, _res, op=op, prescale_factor=prescale_factor)
                kind = "hier"
            elif quant_bucket:
                from ..quant import collectives as qc

                state = qc.quantized_allreduce_start(
                    flat, axis, op=op, prescale_factor=prescale_factor,
                    wire=quant_leg)
                kind = "quant"
            else:
                state = dev.allreduce(flat, axis, op, prescale_factor,
                                      postscale_factor)
                kind = "plain"
        issued.append((bucket, shapes, sizes, orig_dtype, kind, state, flat))

    from ..quant.collectives import wire_sentinel as _sentinel

    _account(bucket_bytes,
             wire=("hierarchical" if hier
                   else _sentinel(quant_leg) if quant_leg is not None
                   else "exact"))

    cells: List[Any] = [None] * len(leaves)
    for k, (bucket, shapes, sizes, orig_dtype, kind, state, _payload) \
            in enumerate(issued):
        pin = (_payload_token(issued[k + 1][6])
               if k + 1 < len(issued) else None)
        if kind == "hier":
            from ..transport import hierarchy as _th

            # Slow finish + allgather of bucket k overlaps bucket k+1's
            # flight window: the inflight arrays are barriered with
            # k+1's payload, never with k+1's result.
            state = _th.pin_inflight(state, pin)
            with jax.named_scope(f"hvdt.overlap.b{k}.finish"):
                red = _th.hierarchical_allreduce_finish(
                    state, postscale_factor)
        elif kind == "quant":
            import dataclasses as _dc

            from ..quant import collectives as qc

            if pin is not None:
                # Dequant-accumulate of bucket k overlaps the wire phase
                # of bucket k+1: the received wire shards are barriered
                # with k+1's payload, never with k+1's result.
                q2, s2, _ = lax.optimization_barrier(
                    (state.q_recv, state.s_recv, pin))
                state = _dc.replace(state, q_recv=q2, s_recv=s2)
            with jax.named_scope(f"hvdt.overlap.b{k}.finish"):
                red = qc.quantized_allreduce_finish(state, postscale_factor)
        else:
            red = state
        if red.dtype != orig_dtype:
            red = red.astype(orig_dtype)
        offset = 0
        for i, shape, sz in zip(bucket, shapes, sizes):
            g = lax.dynamic_slice_in_dim(red, offset, sz).reshape(shape)
            offset += sz
            cells[i] = g if leaf_finish is None else leaf_finish(i, g, pin)
    return cells, token


class OverlapScheduler:
    """Dependency-ordered bucketed exchange — the ``HVDT_OVERLAP=on``
    replacement for the monolithic ``fused_allreduce`` (same signature,
    same semantics, overlapped schedule).  Stateless: safe to share
    across threads and jit traces."""

    def exchange(self, tree, axis="dp", op: ReduceOp = ReduceOp.AVERAGE,
                 threshold_bytes: Optional[int] = None,
                 prescale_factor: float = 1.0,
                 postscale_factor: float = 1.0,
                 wire_dtype: Optional[Any] = None):
        """Drop-in for ``ops.device.fused_allreduce`` with the
        reverse-topological, barrier-pinned bucket schedule.  Bitwise
        identical results for exact wires (psum is elementwise — any
        bucketing slices out the same values); the int8 wire keeps the
        established block-scale/2 bound per stage."""
        from ..transport import policy as _tpolicy

        from ..quant.collectives import quant_wire_leg as _qleg

        threshold_bytes = dev._validated_threshold(
            _tpolicy.bucket_threshold(axis, threshold_bytes))
        quant_wire = _qleg(wire_dtype)
        if quant_wire is not None:
            wire_dtype = None  # the quantized path owns the wire format
        leaves, treedef = jax.tree.flatten(tree)
        if not leaves:
            return tree
        cells, _token = _exchange_leaves(
            leaves, axis, op, threshold_bytes, prescale_factor,
            postscale_factor, wire_dtype, quant_wire, token=None)
        return jax.tree.unflatten(treedef, cells)


# ---------------------------------------------------------------------------
# Segmented VJP: per-bucket backward segments with the exchange issued
# between them — the traced program itself interleaves collectives with
# VJP compute (the lowered-HLO contract).
# ---------------------------------------------------------------------------


def overlap_value_and_grad(stage_fns: Sequence[Callable],
                           axis="dp", op: ReduceOp = ReduceOp.AVERAGE, *,
                           threshold_bytes: Optional[int] = None,
                           prescale_factor: float = 1.0,
                           postscale_factor: float = 1.0,
                           wire_dtype: Optional[Any] = None,
                           reduce_grads: bool = True) -> Callable:
    """Value-and-grad over a chain of stages with each stage's gradient
    exchange issued as soon as that VJP segment's grads exist.

    ``stage_fns``: sequence of ``f_i(params_i, x) -> x``; the LAST stage
    must return a scalar loss.  Returns ``fn(params_seq, x) -> (loss,
    grads_seq)`` where ``grads_seq[i]`` is stage i's gradient pytree,
    already allreduced over ``axis`` (dependency-ordered: stage i's
    collective is issued between VJP segment i and segment i-1, and the
    upstream cotangent is barriered with the stage's payload token so
    XLA cannot hoist the remaining backward above the issue point).
    ``reduce_grads=False`` skips the exchange (raw per-shard grads) —
    the A/B leg for measuring the exchange itself.

    Valid inside shard_map where ``axis`` is bound, like every
    collective in ops/device.py.
    """
    stage_fns = tuple(stage_fns)
    if not stage_fns:
        raise ValueError("overlap_value_and_grad needs at least one stage")

    def fn(params_seq, x):
        params_seq = list(params_seq)
        if len(params_seq) != len(stage_fns):
            raise ValueError(
                f"{len(params_seq)} param trees for {len(stage_fns)} stages")
        vjps = []
        act = x
        for f, p in zip(stage_fns, params_seq):
            act, vjp = jax.vjp(f, p, act)
            vjps.append(vjp)
        loss = act
        if getattr(loss, "shape", ()) != ():
            raise ValueError("the last stage must return a scalar loss")

        from ..transport import policy as _tpolicy

        threshold = dev._validated_threshold(
            _tpolicy.bucket_threshold(axis, threshold_bytes))
        from ..quant.collectives import quant_wire_leg as _qleg
        from ..quant.collectives import wire_sentinel as _sentinel

        quant_wire = _qleg(wire_dtype)
        wd = wire_dtype if quant_wire is None else None

        # ZeRO composition (ops/zero.py): with HVDT_ZERO live, each VJP
        # segment's exchange rides the reduce-scatter wire (rs_exchange:
        # per-bucket reduce-scatter + invariant allgather, itself
        # payload-chain pinned when this scheduler is on) — the traced
        # program interleaves reduce-scatters with backward compute,
        # the lowered-HLO contract tests/test_zero.py pins.
        from . import zero as _zero

        zero_stage = _zero.stage()

        grads: List[Any] = [None] * len(stage_fns)
        token = None
        ct = jnp.ones_like(loss)
        for i in reversed(range(len(stage_fns))):
            with jax.named_scope(f"hvdt.overlap.vjp_seg{i}"):
                g_p, ct = vjps[i](ct)
            if reduce_grads:
                leaves, treedef = jax.tree.flatten(g_p)
                if leaves and zero_stage is not None:
                    g_p = _zero.rs_exchange(
                        g_p, axis, op, threshold_bytes=threshold,
                        prescale_factor=prescale_factor,
                        postscale_factor=postscale_factor,
                        wire_dtype=wd if quant_wire is None
                        else _sentinel(quant_wire))
                    token = _payload_token(jnp.ravel(leaves[0]))
                    if i > 0:
                        ct, _ = lax.optimization_barrier((ct, token))
                elif leaves:
                    cells, token = _exchange_leaves(
                        leaves, axis, op, threshold, prescale_factor,
                        postscale_factor, wd, quant_wire, token)
                    g_p = jax.tree.unflatten(treedef, cells)
                    if i > 0 and token is not None:
                        # Pin the issue point BETWEEN VJP segments: the
                        # upstream cotangent is barriered with this
                        # stage's payload token, so segment i-1's compute
                        # is scheduled after stage i's exchange is issued
                        # (and the exchange cannot sink below it).
                        ct, _ = lax.optimization_barrier((ct, token))
            grads[i] = g_p
        return loss, grads

    return fn


# ---------------------------------------------------------------------------
# Pallas latency-hiding leg: pipelined exchange + fused optimizer update
# ---------------------------------------------------------------------------


def exchange_and_update(grads, leaf_update: Callable, aux_trees=(),
                        axis="dp", op: ReduceOp = ReduceOp.AVERAGE, *,
                        threshold_bytes: Optional[int] = None,
                        prescale_factor: float = 1.0,
                        postscale_factor: float = 1.0,
                        wire_dtype: Optional[Any] = None):
    """Pipelined gradient exchange fused with the per-leaf optimizer
    update: bucket N's update runs while bucket N+1's collective is in
    flight, so the optimizer is no longer a serial epilogue after the
    last collective (the Pallas latency-hiding leg — pair with the
    single-HBM-pass units in ops/optim_kernels:
    ``sgd_leaf_update`` / ``adam_leaf_update``).

    ``leaf_update(reduced_grad, *aux_leaves) -> out`` (array or tuple of
    arrays); ``aux_trees`` are pytrees congruent with ``grads`` whose
    leaves ride along (momentum/moment buffers, params).  Returns a
    pytree matching ``grads`` — or a tuple of such pytrees when
    ``leaf_update`` returns tuples (e.g. ``(updates, new_trace)``).
    """
    from ..quant.collectives import quant_wire_leg as _qleg

    threshold_bytes = dev._validated_threshold(threshold_bytes)
    quant_wire = _qleg(wire_dtype)
    if quant_wire is not None:
        wire_dtype = None
    leaves, treedef = jax.tree.flatten(grads)
    if not leaves:
        return grads
    aux_leaves = [treedef.flatten_up_to(t) for t in aux_trees]

    def finish(i, g, pin):
        aux = [a[i] for a in aux_leaves]
        if pin is not None:
            # The update of this bucket is scheduled under the NEXT
            # collective's flight window: its inputs are barriered with
            # the next bucket's payload (never its result).
            pinned = lax.optimization_barrier(tuple([g] + aux) + (pin,))
            g, aux = pinned[0], list(pinned[1:-1])
        return leaf_update(g, *aux)

    cells, _token = _exchange_leaves(
        leaves, axis, op, threshold_bytes, prescale_factor,
        postscale_factor, wire_dtype, quant_wire, token=None,
        leaf_finish=finish)
    if cells and isinstance(cells[0], (tuple, list)):
        width = len(cells[0])
        return tuple(jax.tree.unflatten(treedef, [c[j] for c in cells])
                     for j in range(width))
    return jax.tree.unflatten(treedef, cells)


def pipelined_sgd(learning_rate, momentum: float = 0.0,
                  nesterov: bool = False, *, axis="dp",
                  op: ReduceOp = ReduceOp.AVERAGE,
                  threshold_bytes: Optional[int] = None,
                  wire_dtype: Optional[Any] = None,
                  use_kernels: bool = True):
    """Drop-in for ``optax.chain(DistributedGradientTransformation(...),
    fused_sgd(...))`` with the exchange and the single-HBM-pass momentum
    update pipelined per bucket (:func:`exchange_and_update`).  Same
    state tree (``optax.TraceState`` — or ``EmptyState`` without
    momentum), same f32-accumulated math, hot-swappable against the
    unpipelined chain mid-run.

    Gradient-aware semantics mirror ``optimizer.allreduce_gradients``:
    leaves unvarying over ``axis`` (already cross-shard summed by modern
    AD) and runs with no bound axis skip the collective and only scale.
    """
    import optax

    if callable(learning_rate):
        raise ValueError(
            "pipelined_sgd takes a float learning_rate (TraceState "
            "carries no step count for a schedule); see fused_adam for "
            "schedule support")

    def init_fn(params):
        if not momentum:
            del params
            return optax.EmptyState()
        return optax.TraceState(trace=jax.tree.map(jnp.zeros_like, params))

    def update_fn(updates, state, params=None):
        del params
        from .optim_kernels import sgd_leaf_update

        scalars = jnp.stack([jnp.asarray(learning_rate, jnp.float32)])

        def upd(g, *aux):
            if not momentum:
                return (-scalars[0] * g.astype(jnp.float32)).astype(g.dtype)
            return sgd_leaf_update(g, aux[0], scalars, momentum=momentum,
                                   nesterov=nesterov,
                                   use_kernels=use_kernels)

        from ..optimizer import _axis_bound

        leaves, treedef = jax.tree.flatten(updates)
        aux = (state.trace,) if momentum else ()
        if not _axis_bound(axis) or not leaves:
            # No bound mesh axis (plain auto-sharded jit): gradients are
            # already global — plain (unpipelined) update.
            aux_leaves = [treedef.flatten_up_to(t) for t in aux]
            cells = [upd(g, *[a[i] for a in aux_leaves])
                     for i, g in enumerate(leaves)]
        else:
            n = 1
            for a in ((axis,) if isinstance(axis, str) else tuple(axis)):
                n *= dev._axis_size_static(a)
            varying = [dev.is_varying(l, axis) for l in leaves]
            scale = (1.0 / n) if op == ReduceOp.AVERAGE else 1.0
            if all(varying):
                out = exchange_and_update(
                    updates, upd, aux_trees=aux, axis=axis, op=op,
                    threshold_bytes=threshold_bytes, wire_dtype=wire_dtype)
                if momentum:
                    deltas, new_m = out
                    return deltas, optax.TraceState(trace=new_m)
                return out, state
            # Mixed/unvarying regime (modern AD pre-summed the cotangent
            # of replicated params): scale instead of reducing.
            aux_leaves = [treedef.flatten_up_to(t) for t in aux]
            cells = []
            for i, g in enumerate(leaves):
                if varying[i]:
                    g = dev.allreduce(g, axis, op)
                elif scale != 1.0:
                    g = g * scale
                cells.append(upd(g, *[a[i] for a in aux_leaves]))
        if momentum:
            deltas = jax.tree.unflatten(treedef, [c[0] for c in cells])
            new_m = jax.tree.unflatten(treedef, [c[1] for c in cells])
            return deltas, optax.TraceState(trace=new_m)
        return jax.tree.unflatten(treedef, cells), state

    return optax.GradientTransformation(init_fn, update_fn)


# ---------------------------------------------------------------------------
# XLA latency-hiding scheduler / async collective fusion engagement
# ---------------------------------------------------------------------------

# XLA:TPU flags that turn dependency freedom into overlapped execution:
# async collective fusion wraps independent compute between a
# collective's (start, done) pair; the continuation/overlap flag lets
# the TensorCore run compute while a collective is in flight.  Ridden
# through the LIBTPU_INIT_ARGS env contract — read once at TPU backend
# init, inert on CPU/GPU backends (the jax-0.4.37-safe engagement: no
# jax API involved at all).
_ASYNC_COLLECTIVE_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
)


def _jax_backend_initialized() -> bool:
    try:
        from jax._src import xla_bridge

        return bool(getattr(xla_bridge, "_backends", None))
    except Exception:
        return False


def enable_latency_hiding(mode: Optional[str] = None) -> Optional[str]:
    """Engage XLA's latency-hiding scheduler / async-collective-fusion
    flags (``HVDT_XLA_LATENCY_HIDING``: auto|on|off).

    ``auto`` (default) appends the flags to ``LIBTPU_INIT_ARGS`` unless
    ``JAX_PLATFORMS`` pins a non-TPU backend (the CPU test mesh keeps
    its environment untouched); ``on`` always appends (the flags are
    inert off-TPU anyway); ``off`` is a no-op.  Idempotent — flags
    already present are never duplicated.  Returns the resulting
    ``LIBTPU_INIT_ARGS`` string, or ``None`` when nothing was engaged.

    Called by ``hvd.init()``; call it before
    the first jax computation — libtpu reads the env once at backend
    init, so flags added later apply to the NEXT process (warned).
    """
    if mode is None:
        mode = config.get_str("HVDT_XLA_LATENCY_HIDING")
    mode = (mode or "auto").strip().lower()
    if mode in ("off", "0", "false", "none", "no"):
        return None
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if mode == "auto" and platforms and "tpu" not in platforms.lower():
        return None
    cur = os.environ.get("LIBTPU_INIT_ARGS", "")
    missing = [f for f in _ASYNC_COLLECTIVE_FLAGS
               if f.split("=", 1)[0] not in cur]
    if not missing:
        return cur or None
    if _jax_backend_initialized():
        log.warning(
            "latency-hiding flags engaged AFTER jax backend init; "
            "LIBTPU_INIT_ARGS is read once at TPU init, so they apply "
            "to the next process")
    os.environ["LIBTPU_INIT_ARGS"] = (cur + " " + " ".join(missing)).strip()
    log.info("XLA latency-hiding flags engaged: %s", " ".join(missing))
    return os.environ["LIBTPU_INIT_ARGS"]
