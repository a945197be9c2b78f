"""Device-level collective primitives — the TPU data plane.

This module is the TPU-native replacement for the reference's entire
collective-op backend stack (ref: ops/mpi_operations.cc, ops/nccl_operations.cc,
ops/gloo_operations.cc, ops/ccl_operations.cc — SURVEY.md §2.2): instead of
hand-written transports, collectives are XLA programs over ICI/DCN expressed
with ``jax.lax`` named-axis primitives.  They are valid inside ``shard_map``
/ ``pjit`` bodies where the named mesh axes are bound.

Design notes (SURVEY.md §5.8): under jit, op order is globally consistent, so
the reference's name-negotiation machinery is unnecessary here — XLA plays the
role of the OperationManager, and fusion is explicit bucketing (see
``fused_allreduce``) mirroring the FusionBufferManager
(ref: common/fusion_buffer_manager.{h,cc}, controller.cc:808 FuseResponses).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from ..common.logging_util import get_logger
from ..common.types import ReduceOp

log = get_logger(__name__)

__all__ = [
    "allreduce",
    "allgather",
    "allgather_ragged",
    "reduce_scatter",
    "broadcast",
    "alltoall",
    "alltoall_uneven",
    "axis_rank",
    "axis_size",
    "fused_allreduce",
    "fused_allreduce_buckets",
    "hierarchical_allreduce",
    "invariant_allgather_shards",
    "reduce_scatter_flat",
    "allgather_flat_shards",
    "shard_owner_index",
]

AxisName = Union[str, Tuple[str, ...]]


def axis_rank(axis: AxisName) -> jax.Array:
    """Rank of this shard along ``axis`` (ref: horovod_rank per communicator)."""
    return lax.axis_index(axis)


def axis_size(axis: AxisName) -> int:
    """Number of shards along ``axis`` (ref: horovod_size)."""
    return _axis_size_static(axis)


def _axes_tuple(axis: AxisName) -> Tuple[str, ...]:
    return (axis,) if isinstance(axis, str) else tuple(axis)


def _axis_size_static(axis: AxisName) -> int:
    """Static size of the bound mesh axis/axes.  Raises (NameError)
    when ``axis`` is not bound, like ``lax.axis_size``."""
    n = 1
    for a in _axes_tuple(axis):
        n *= int(lax.axis_size(a))
    return n


def _vma_tracking_active(axis: AxisName) -> bool:
    """True when varying-manual-axes tracking is live for ``axis`` in the
    current trace.  Under ``shard_map(..., check_vma=False)`` every aval
    reports an empty vma, which would be indistinguishable from "genuinely
    replicated" — probe with a pcast: if even an explicitly-varied zero
    reports an empty vma, tracking is off and callers must assume varying.
    """
    import jax.numpy as jnp

    for a in _axes_tuple(axis):
        try:
            probe = lax.pcast(jnp.zeros((), jnp.float32), a, to="varying")
            if a not in jax.typeof(probe).vma:
                return False
        except Exception:
            return False
    return True


def is_varying(x, axis: AxisName) -> bool:
    """Whether ``x`` is varying (per-shard distinct) over ``axis`` under
    JAX's varying-manual-axes tracking (jax>=0.8 shard_map).

    Load-bearing semantics note: in modern JAX, ``jax.grad`` taken inside
    ``shard_map`` w.r.t. a *replicated* (unvarying) parameter already
    returns the cross-shard SUM of per-shard gradients — the AD system
    inserts the psum to keep the cotangent unvarying.  An allreduce on such
    a value must therefore not psum again; the varying-aware fast paths
    below keep Horovod allreduce semantics exact in both regimes.

    Conservatively returns True (collective WILL be issued) whenever
    tracking cannot be positively confirmed: eager, or
    ``check_vma=False`` shard_maps.
    """
    if not _vma_tracking_active(axis):
        return True
    try:
        vma = jax.typeof(x).vma
    except Exception:
        return True
    return any(a in vma for a in _axes_tuple(axis))


def allreduce(x, axis: AxisName = "dp", op: ReduceOp = ReduceOp.AVERAGE,
              prescale_factor: float = 1.0, postscale_factor: float = 1.0):
    """Allreduce over a mesh axis (ref: EnqueueTensorAllreduce
    operations.cc:1357; NCCLAllreduce::Execute nccl_operations.cc:175).

    Average is implemented as sum + postscale by 1/size, matching the
    reference's prescale/postscale split (torch/optimizer.py:197-204) —
    XLA folds the scales into neighbouring ops.
    """
    if prescale_factor != 1.0:
        x = jax.tree.map(lambda t: t * prescale_factor, x)

    # Varying-aware fast path: an unvarying input is identical on every
    # shard, so the reduction is a scalar identity and no collective is
    # needed.  SEMANTICS: this treats x as "the per-rank value" — average
    # of n identical copies is x, sum is n*x (exactly what a psum would
    # return, minus the collective).  For GRADIENTS of replicated params,
    # which modern AD delivers pre-summed, use
    # optimizer.allreduce_gradients — it applies the gradient-aware
    # interpretation (average = x/n) instead.
    leaves = jax.tree.leaves(x)
    if leaves and all(not is_varying(t, axis) for t in leaves):
        n = 1
        for a in _axes_tuple(axis):
            n *= _axis_size_static(a)
        if op == ReduceOp.SUM:
            out = jax.tree.map(lambda t: t * n, x)
        elif op in (ReduceOp.AVERAGE, ReduceOp.MIN, ReduceOp.MAX,
                    ReduceOp.ADASUM):
            out = x
        elif op == ReduceOp.PRODUCT:
            out = jax.tree.map(lambda t: t ** n, x)
        else:
            raise ValueError(f"Unsupported reduce op: {op}")
        if postscale_factor != 1.0:
            out = jax.tree.map(lambda t: t * postscale_factor, out)
        return out

    if op in (ReduceOp.AVERAGE, ReduceOp.SUM):
        out = lax.psum(x, axis)
        if op == ReduceOp.AVERAGE:
            n = _axis_size_static(axis)
            out = jax.tree.map(lambda t: t / n, out)
    elif op == ReduceOp.MIN:
        out = lax.pmin(x, axis)
    elif op == ReduceOp.MAX:
        out = lax.pmax(x, axis)
    elif op == ReduceOp.PRODUCT:
        # exp(psum(log|x|)) with explicit sign/zero tracking so arbitrary
        # reals reduce correctly (log of a negative would poison the psum).
        def _prod(t):
            mag = jnp.exp(lax.psum(jnp.log(jnp.where(t == 0, 1.0, jnp.abs(t))), axis))
            n_neg = lax.psum((t < 0).astype(jnp.int32), axis)
            any_zero = lax.psum((t == 0).astype(jnp.int32), axis) > 0
            signed = jnp.where(n_neg % 2 == 1, -mag, mag)
            return jnp.where(any_zero, 0.0, signed).astype(t.dtype)

        out = jax.tree.map(_prod, x)
    elif op == ReduceOp.ADASUM:
        from . import adasum as _adasum

        out = _adasum.adasum_allreduce(x, axis)
    else:
        raise ValueError(f"Unsupported reduce op: {op}")
    if postscale_factor != 1.0:
        out = jax.tree.map(lambda t: t * postscale_factor, out)
    return out


def allgather(x, axis: AxisName = "dp", concat_axis: int = 0, *, tiled: bool = True):
    """Allgather over a mesh axis, concatenating along ``concat_axis``
    (ref: EnqueueTensorAllgather; AllgatherOp displacement math
    ops/collective_operations.h:129).  Unlike the reference, first-dimension
    ragged gathers are not supported under jit (static shapes); use the eager
    path for ragged inputs."""
    return jax.tree.map(
        lambda t: lax.all_gather(t, axis, axis=concat_axis, tiled=tiled), x)


def reduce_scatter(x, axis: AxisName = "dp", scatter_axis: int = 0,
                   op: ReduceOp = ReduceOp.SUM):
    """Reduce-scatter over a mesh axis — first-class on TPU (building block
    for ZeRO/FSDP-style sharding and Adasum; the reference only has it
    embedded inside NCCLHierarchicalAllreduce, nccl_operations.cc:378).

    SUM/AVERAGE lower to ``psum_scatter`` (the native ICI reduction).
    MIN/MAX/PRODUCT have no scatter-reduce XLA primitive, so they lower to
    the bandwidth-equivalent all-to-all + local reduce: each element
    crosses the wire exactly once, then n shard-copies reduce locally —
    the same wire cost as a ring reduce-scatter (the reference's dispatch
    handles these ops generically, ops/collective_operations.h:209-273)."""
    def _rs(t):
        if op in (ReduceOp.SUM, ReduceOp.AVERAGE):
            out = lax.psum_scatter(t, axis, scatter_dimension=scatter_axis,
                                   tiled=True)
            if op == ReduceOp.AVERAGE:
                out = out / _axis_size_static(axis)
            return out
        n = _axis_size_static(axis)
        if t.shape[scatter_axis] % n:
            raise ValueError(
                f"reduce_scatter dim {scatter_axis} ({t.shape[scatter_axis]}) "
                f"not divisible by axis size {n}")
        # rank r receives every rank's r'th slice, stacked along
        # scatter_axis: [..., n*chunk, ...] -> [..., n, chunk, ...]
        gathered = lax.all_to_all(t, axis, split_axis=scatter_axis,
                                  concat_axis=scatter_axis, tiled=True)
        chunk = t.shape[scatter_axis] // n
        shape = (gathered.shape[:scatter_axis] + (n, chunk)
                 + gathered.shape[scatter_axis + 1:])
        stacked = gathered.reshape(shape)
        if op == ReduceOp.MIN:
            return jnp.min(stacked, axis=scatter_axis)
        if op == ReduceOp.MAX:
            return jnp.max(stacked, axis=scatter_axis)
        if op == ReduceOp.PRODUCT:
            return jnp.prod(stacked, axis=scatter_axis)
        raise ValueError(f"Unsupported reduce op: {op}")

    return jax.tree.map(_rs, x)


def allgather_ragged(x, sizes: Sequence[int], axis: AxisName = "dp"):
    """Allgather where rank r contributes its first ``sizes[r]`` rows —
    the jit-path answer to the reference's first-dimension-ragged allgather
    (AllgatherOp displacement math, ops/collective_operations.h:129).

    ``sizes`` must be static (known at trace time): XLA needs static
    shapes, so the dynamic-shape negotiation the reference does at runtime
    moves to trace time here.  Every rank passes a uniformly padded array
    with ``max(sizes)`` rows (SPMD requires identical per-rank shapes);
    rows past ``sizes[rank]`` are ignored.  Returns the exact
    ``sum(sizes)``-row concatenation, replicated (axis-invariant).

    Lowering: each rank zero-embeds its valid rows at its static
    displacement and the result is one psum — gather and invariance
    restoration fused into a single all-reduce (see
    ``invariant_allgather_shards`` for the equal-shard case).
    """
    sizes = [int(s) for s in sizes]
    n = _axis_size_static(axis)
    if len(sizes) != n:
        raise ValueError(f"len(sizes)={len(sizes)} != axis size {n}")
    maxpad = max(sizes)
    total = sum(sizes)
    offsets = jnp.asarray(
        [sum(sizes[:r]) for r in range(n)], jnp.int32)
    sizes_arr = jnp.asarray(sizes, jnp.int32)
    idx = lax.axis_index(axis)

    def _one(t):
        if t.shape[0] != maxpad:
            raise ValueError(
                f"ragged allgather input must be padded to max(sizes)="
                f"{maxpad} rows, got {t.shape[0]}")
        mask_shape = (maxpad,) + (1,) * (t.ndim - 1)
        mask = (jnp.arange(maxpad) < sizes_arr[idx]).reshape(mask_shape)
        contrib = jnp.where(mask, t, jnp.zeros((), t.dtype))
        # Embed into total+maxpad rows so the padded block never clamps;
        # masked-zero overhang rows land in the next rank's region and
        # add nothing under psum.
        buf = jnp.zeros((total + maxpad,) + t.shape[1:], t.dtype)
        buf = lax.dynamic_update_slice_in_dim(buf, contrib, offsets[idx],
                                              axis=0)
        return lax.psum(buf, axis)[:total]

    return jax.tree.map(_one, x)


def alltoall_uneven(x, send_splits: Sequence[Sequence[int]],
                    axis: AxisName = "dp"):
    """All-to-all with per-(src, dst) row counts — the jit-path analog of
    the reference's alltoallv (AlltoallOp::PrepareOutputAndParams recv-
    split exchange, ops/collective_operations.h:209-273).

    ``send_splits[r][j]`` = rows rank r sends to rank j, static at trace
    time (the runtime recv-split MPI exchange moves to trace time under
    XLA's static-shape model).  Each rank's row counts must sum to the
    (uniform) input first dimension.  Because received totals differ per
    rank while SPMD output shapes cannot, the result is padded to the
    largest receive total; returns ``(out, recv_count)`` where ``out`` has
    ``max_j(sum_r send_splits[r][j])`` rows (rows past ``recv_count`` are
    zero) and ``recv_count`` is this rank's valid-row scalar.

    Wire cost: segments are padded to the largest single split for the
    device all_to_all — bounded overhead for near-even splits (the MoE
    capacity-padding regime this substrate targets, SURVEY.md §2.7);
    grossly skewed splits pay padding bandwidth.
    """
    M = [[int(v) for v in row] for row in send_splits]
    n = _axis_size_static(axis)
    if len(M) != n or any(len(row) != n for row in M):
        raise ValueError(f"send_splits must be {n}x{n}")
    row_tot = {sum(row) for row in M}
    if len(row_tot) != 1:
        raise ValueError(
            "each rank's send_splits row must sum to the same (uniform) "
            f"input length, got sums {sorted(row_tot)}")
    in_rows = row_tot.pop()
    maxseg = max(max(row) for row in M)
    recv_totals = [sum(M[r][j] for r in range(n)) for j in range(n)]
    max_out = max(recv_totals)

    send_off = jnp.asarray(
        [[sum(row[:j]) for j in range(n)] for row in M], jnp.int32)
    seg_len = jnp.asarray(M, jnp.int32)
    recv_off = jnp.asarray(
        [[sum(M[k][j] for k in range(r)) for j in range(n)]
         for r in range(n)], jnp.int32)
    recv_tot = jnp.asarray(recv_totals, jnp.int32)
    idx = lax.axis_index(axis)

    def _one(t):
        if t.shape[0] != in_rows:
            raise ValueError(
                f"input rows {t.shape[0]} != send_splits row sum {in_rows}")
        pad = jnp.zeros((maxseg,) + t.shape[1:], t.dtype)
        tp = jnp.concatenate([t, pad], axis=0)
        segs = []
        for j in range(n):
            seg = lax.dynamic_slice_in_dim(tp, send_off[idx, j], maxseg,
                                           axis=0)
            mask = (jnp.arange(maxseg) < seg_len[idx, j]).reshape(
                (maxseg,) + (1,) * (t.ndim - 1))
            segs.append(jnp.where(mask, seg, jnp.zeros((), t.dtype)))
        sendbuf = jnp.concatenate(segs, axis=0)        # [n*maxseg, ...]
        recvbuf = lax.all_to_all(sendbuf, axis, split_axis=0,
                                 concat_axis=0, tiled=True)
        out = jnp.zeros((max_out + maxseg,) + t.shape[1:], t.dtype)
        for r in range(n):
            block = lax.dynamic_slice_in_dim(recvbuf, r * maxseg, maxseg,
                                             axis=0)
            # blocks are already masked by the sender; valid regions are
            # disjoint, so additive embedding assembles the compaction.
            embed = jnp.zeros_like(out)
            embed = lax.dynamic_update_slice_in_dim(
                embed, block, recv_off[r, idx], axis=0)
            out = out + embed
        return out[:max_out]

    return jax.tree.map(_one, x), recv_tot[idx]


def broadcast(x, root_rank: int = 0, axis: AxisName = "dp"):
    """Broadcast from ``root_rank``'s shard to all shards along ``axis``
    (ref: EnqueueTensorBroadcast; NCCLBroadcast nccl_operations.cc:535).

    Implemented as a masked psum — the idiomatic XLA lowering (a one-hot
    select then all-reduce rides the same ICI reduction tree as a native
    broadcast)."""
    idx = lax.axis_index(axis)

    def _bcast(t):
        # where (not multiply) so NaN/Inf in non-root shards — e.g.
        # uninitialized buffers being overwritten by the broadcast — cannot
        # poison the psum.
        zero = jnp.zeros((), dtype=jnp.int32 if t.dtype == jnp.bool_ else t.dtype)
        contrib = jnp.where(idx == root_rank,
                            t.astype(zero.dtype) if t.dtype == jnp.bool_ else t,
                            zero)
        out = lax.psum(contrib, axis)
        return (out != 0) if t.dtype == jnp.bool_ else out

    return jax.tree.map(_bcast, x)


def alltoall(x, axis: AxisName = "dp", split_axis: int = 0, concat_axis: int = 0):
    """All-to-all over a mesh axis (ref: EnqueueTensorAlltoall
    operations.cc:1642; AlltoallOp ops/collective_operations.h:195).

    Equal splits only under jit (static shapes); the eager path handles
    uneven splits.  This is the substrate for expert parallelism (MoE token
    routing) — SURVEY.md §2.7."""
    return jax.tree.map(
        lambda t: lax.all_to_all(t, axis, split_axis=split_axis,
                                 concat_axis=concat_axis, tiled=True), x)


# ---------------------------------------------------------------------------
# Tensor fusion: bucketed fused allreduce over a pytree of gradients.
# (ref: FusionBufferManager common/fusion_buffer_manager.{h,cc};
#  FuseResponses controller.cc:808; fused memcpy collective_operations.cc.)
# On TPU a bucket is a collective, not a buffer: NCCL wants one pointer, XLA
# takes several operands in one all-reduce (its combiner builds it from the
# per-leaf all_reduces one psum of a list lowers to), so the leaves of a
# (dtype, bucket) go in their own shapes.  Only a wire that cuts the payload
# into blocks or shards (or Adasum, whose dot products span it) packs a flat
# vector.
# ---------------------------------------------------------------------------

_threshold_warned = False


def _validated_threshold(threshold_bytes: Optional[Any] = None) -> int:
    """Resolve and validate the fusion threshold.

    ``None`` reads ``HVDT_FUSION_THRESHOLD``.  Non-positive or
    unparseable values (env garbage, a caller passing 0/-1) must not
    flow into bucket planning — a threshold of 0 would put every leaf
    in its own bucket and a negative one is meaningless — so they clamp
    to the registry default with a one-time warning."""
    global _threshold_warned
    from ..common import config

    if threshold_bytes is None:
        threshold_bytes = config.get_int("HVDT_FUSION_THRESHOLD")
    try:
        t = int(threshold_bytes)
    except (TypeError, ValueError):
        t = -1
    if t <= 0:
        default = int(config.KNOBS["HVDT_FUSION_THRESHOLD"].default)
        if not _threshold_warned:
            log.warning(
                "invalid fusion threshold %r (HVDT_FUSION_THRESHOLD or "
                "caller override); clamping to the default %d bytes",
                threshold_bytes, default)
            _threshold_warned = True
        return default
    return t


def fused_allreduce_buckets(leaves: Sequence[jax.Array],
                            threshold_bytes: int) -> List[List[int]]:
    """Plan fusion buckets: group leaf indices by dtype, pack up to
    ``threshold_bytes`` per bucket (64-byte alignment unit like the
    reference, common.h:147 — moot on TPU but kept for parity of the plan).

    Pure planning function; host-side, shape-only.  Deterministic:
    dtype groups are emitted in canonical (dtype-name) order, not dict
    insertion order, so the plan does not depend on which dtype happens
    to appear first in ``leaves`` — same leaves, any interleaving of
    dtypes → same bucket plan (within a dtype, input order is preserved:
    it is the reverse-topological adjacency the overlap schedule needs).
    """
    threshold_bytes = _validated_threshold(threshold_bytes)
    by_dtype: Dict[Any, List[int]] = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(jnp.result_type(leaf), []).append(i)
    buckets: List[List[int]] = []
    for dtype, idxs in sorted(by_dtype.items(),
                              key=lambda kv: jnp.dtype(kv[0]).name):
        cur: List[int] = []
        cur_bytes = 0
        itemsize = jnp.dtype(dtype).itemsize
        for i in idxs:
            nbytes = -(-leaves[i].size * itemsize // 64) * 64
            if cur and cur_bytes + nbytes > threshold_bytes:
                buckets.append(cur)
                cur, cur_bytes = [], 0
            cur.append(i)
            cur_bytes += nbytes
        if cur:
            buckets.append(cur)
    return buckets


def fused_allreduce(tree, axis: AxisName = "dp", op: ReduceOp = ReduceOp.AVERAGE,
                    threshold_bytes: Optional[int] = None,
                    prescale_factor: float = 1.0,
                    postscale_factor: float = 1.0,
                    wire_dtype: Optional[Any] = None):
    """Allreduce a pytree as few fused collectives (the hot path of
    DistributedOptimizer — ref call stack SURVEY.md §3.2).

    A bucket (``fused_allreduce_buckets``: same dtype, up to
    ``threshold_bytes``) is one collective, not a buffer.  What it
    carries follows the wire, which the code can observe, and no knob: an
    elementwise reduction on the exact or a cast wire hands the bucket's
    leaves to one ``psum`` in their own shapes (XLA combines the
    operands into a variadic all-reduce) and returns the results as they
    come (no ravel / concatenate before, no slice / reshape after: on a
    TPU those were physical relayouts of every tiled leaf); the
    block-scaled, hierarchical and Adasum reductions below work on the
    payload's whole extent and keep one flat vector per bucket.  Same
    sums either way, bit for bit.

    ``wire_dtype`` optionally casts buckets for the reduction (bf16 wire
    compression — ref: tensorflow/compression.py:141) and casts back.
    The sentinels ``"int8_blockwise"`` / ``"int4_blockwise"``
    (``Compression.int8`` / ``.int4`` ``wire_dtype``, ==
    quant.collectives INT8_WIRE/INT4_WIRE) instead route each float
    bucket through the two-stage block-scaled quantized allreduce —
    real int8 (or packed int4) payloads on the wire, f32 accumulation
    in the middle; non-float buckets keep the exact path.

    Transport policies (``HVDT_TRANSPORT``, horovod_tpu/transport): when
    the active policy resolves ``axis``, float SUM/AVERAGE buckets route
    through the two-level hierarchical allreduce (fast-axis
    reduce-scatter → slow-axis shard exchange → allgather) with the
    per-axis algorithm/wire/threshold the policy names; a single-axis
    flat resolution only overrides the wire/threshold.  Unset (the
    default) leaves this function's program byte-identical — the policy
    lookup is one env read at trace time.
    """
    from ..transport import policy as _tpolicy

    _res = _tpolicy.resolve_axis(axis)
    if threshold_bytes is None and _res is not None:
        threshold_bytes = _res.threshold_bytes
    threshold_bytes = _validated_threshold(threshold_bytes)

    if _res is not None and _res.kind == "flat" and wire_dtype is None:
        # Per-axis wire override for the single-axis flat path (the
        # policy's exact-name / ici-class entry); an explicit caller
        # wire (Compression) keeps precedence.
        wire_dtype = {"bf16": jnp.bfloat16, "fp16": jnp.float16,
                      "int8": "int8_blockwise",
                      "int4": "int4_blockwise"}.get(_res.fast.wire)

    from ..quant.collectives import quant_wire_leg as _qleg

    quant_leg = _qleg(wire_dtype)
    quant_wire = quant_leg is not None
    if quant_wire:
        wire_dtype = None  # the quantized path owns the wire format
    hier = (_res is not None and _res.kind == "hierarchical"
            and op in (ReduceOp.SUM, ReduceOp.AVERAGE))

    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        return tree
    buckets = fused_allreduce_buckets(leaves, threshold_bytes)

    # Telemetry (trace time): under jit the compiled program, not this
    # host code, executes the collectives — so jit-path counters are
    # labelled path=jit and count traced bucket programs (the quantized
    # branch records its own wire accounting inside
    # quantized_allreduce_flat).
    from ..telemetry import instrument as _ti
    from ..telemetry import flight_recorder as _frm

    _rec = _ti.get_recorder()
    _flight = _frm.get_flight_recorder()

    _axis_label = "+".join(_axes_tuple(axis))
    out_leaves: List[Optional[jax.Array]] = [None] * len(leaves)
    for bi, bucket in enumerate(buckets):
        parts = [leaves[i] for i in bucket]
        orig_dtype = jnp.result_type(parts[0])
        float_bucket = jnp.issubdtype(orig_dtype, jnp.floating)
        hier_bucket = hier and float_bucket
        quant_bucket = quant_wire and float_bucket
        # The payload's form follows the wire.  An elementwise reduction
        # takes the leaves as they are; the block-scaled and
        # reduce-scatter wires cut the payload into blocks / shards, and
        # Adasum's dot products run over its whole extent, so those three
        # keep one flat vector per bucket.
        as_leaves = not (hier_bucket or quant_bucket
                         or op == ReduceOp.ADASUM)
        sent = parts if as_leaves else [
            jnp.concatenate([jnp.ravel(p) for p in parts])
            if len(parts) > 1 else jnp.ravel(parts[0])]
        if wire_dtype is not None and orig_dtype != wire_dtype \
                and not hier_bucket:
            sent = [p.astype(wire_dtype) for p in sent]
        if _rec is not None or _flight is not None:
            wire_name = jnp.dtype(sent[0].dtype).name
            bucket_size = sum(int(p.size) for p in sent)
            bucket_bytes = bucket_size * jnp.dtype(sent[0].dtype).itemsize
            payload = "leaves" if as_leaves else "flat"
            if _rec is not None:
                _rec.observe_fusion_fill(
                    bucket_bytes / float(threshold_bytes))
                if not quant_bucket and not hier_bucket:
                    _rec.record_collective(
                        "allreduce", jnp.dtype(orig_dtype).name,
                        wire_name, bucket_bytes, count=len(bucket),
                        path="jit", axis=_axis_label, payload=payload)
            if _flight is not None and not quant_bucket:
                # One traced event per compiled bucket program (under jit
                # the program, not this host code, runs the collective).
                _flight.record(
                    op="allreduce",
                    name=f"hier.b{bi}" if hier_bucket else f"fused.b{bi}",
                    dtype=jnp.dtype(orig_dtype).name,
                    shape=(bucket_size,), nbytes=bucket_bytes,
                    wire=(f"{_res.fast.wire}/{_res.slow.wire}"
                          if hier_bucket else wire_name),
                    path="jit", count=len(bucket), axis=_axis_label,
                    payload=payload)
        # Named scope per fused bucket — the jit-trace analog of the
        # reference's NVTX op ranges; buckets appear as
        # hvdt.fused_allreduce.bN in XPlane/profiler output.
        with jax.named_scope(f"hvdt.fused_allreduce.b{bi}"):
            if hier_bucket:
                from ..transport.hierarchy import hierarchical_allreduce_flat

                red = hierarchical_allreduce_flat(
                    sent[0], _res, op=op,
                    prescale_factor=prescale_factor,
                    postscale_factor=postscale_factor)
            elif quant_bucket:
                from ..quant.collectives import quantized_allreduce_flat

                red = quantized_allreduce_flat(
                    sent[0], axis, op=op,
                    prescale_factor=prescale_factor,
                    postscale_factor=postscale_factor,
                    wire=quant_leg)
            else:
                red = allreduce(sent if as_leaves else sent[0], axis, op,
                                prescale_factor, postscale_factor)
        if as_leaves:
            for i, r in zip(bucket, red):
                out_leaves[i] = r.astype(orig_dtype)
            continue
        red = red.astype(orig_dtype)
        offset = 0
        for i in bucket:
            sz = leaves[i].size
            out_leaves[i] = lax.dynamic_slice_in_dim(
                red, offset, sz).reshape(leaves[i].shape)
            offset += sz
    return jax.tree.unflatten(treedef, out_leaves)


def invariant_allgather_shards(shard, axis: AxisName):
    """Reassemble equal shards into the full vector with an *invariant*
    result type: each rank zero-embeds its shard at its offset and the
    full vector is the psum.

    Rationale: every data-moving collective (all_gather/all_to_all/
    psum_scatter) keeps the varying-manual-axes type, so a pipeline that
    must end replicated (out_specs=P()) needs a psum-family terminal op;
    this fuses the gather and the invariance restoration into one
    allreduce instead of all_gather + identity pmean.
    shard: [chunk, ...]; returns [axis_size*chunk, ...]."""
    n = _axis_size_static(axis)
    idx = lax.axis_index(axis)
    chunk = shard.shape[0]
    full = jnp.zeros((n * chunk,) + shard.shape[1:], shard.dtype)
    full = lax.dynamic_update_slice_in_dim(full, shard, idx * chunk, axis=0)
    return lax.psum(full, axis)


def _rs_hop_order(axis: AxisName) -> Tuple[str, ...]:
    """Sequential reduce-scatter hop order over a reduce group:
    innermost (ICI) axis first, so the full payload rides the fast
    links and only the 1/n_fast shard crosses the slow outer tier (the
    mesh convention: outer axes are the slow ones)."""
    return tuple(reversed(_axes_tuple(axis)))


def reduce_scatter_flat(flat, axis: AxisName):
    """Tiled reduce-scatter of a flat vector over a (possibly
    multi-axis) reduce group: one ``psum_scatter`` hop per axis in
    :func:`_rs_hop_order`.  ``flat``'s length must divide by the group
    size.  Rank ``shard_owner_index(axis)`` receives its contiguous
    1/n chunk of the fully reduced vector — the ZeRO wire primitive
    (ops/zero.py) and the ``bench_allreduce --reduce-scatter`` leg."""
    shard = flat
    for a in _rs_hop_order(axis):
        shard = lax.psum_scatter(shard, a, tiled=True)
    return shard


def allgather_flat_shards(shard, axis: AxisName):
    """Inverse of :func:`reduce_scatter_flat`: invariant zero-embed +
    psum reassembly per axis in reverse hop order, so the result is
    *replicated* over the whole group (P() out_specs / optax.MultiSteps
    type stability — see :func:`invariant_allgather_shards`)."""
    full = shard
    for a in reversed(_rs_hop_order(axis)):
        full = invariant_allgather_shards(full, a)
    return full


def shard_owner_index(axis: AxisName):
    """Linearized chunk index this rank owns after
    :func:`reduce_scatter_flat` (most-significant digit = first RS
    hop).  Trace-time value; ``axis`` must be bound."""
    idx = None
    for a in _rs_hop_order(axis):
        k = _axis_size_static(a)
        i = lax.axis_index(a)
        idx = i if idx is None else idx * k + i
    return idx


def hierarchical_allreduce(x, inner_axis: AxisName = "ici",
                           outer_axis: AxisName = "dcn",
                           op: ReduceOp = ReduceOp.AVERAGE,
                           prescale_factor: float = 1.0,
                           postscale_factor: float = 1.0):
    """Two-level allreduce: reduce-scatter over the fast inner axis,
    allreduce the 1/n_inner shard over the slow outer axis, reassemble
    over inner (ref: NCCLHierarchicalAllreduce — local ncclReduceScatter
    → cross-node MPI_Allreduce → local ncclAllGather,
    nccl_operations.cc:249-517).

    On TPU the natural mapping is inner=ICI (within a slice), outer=DCN
    (between slices): outer-axis wire bytes drop to G/n_inner per chip.
    XLA's GSPMD often derives this itself for plain psum over both axes;
    this op makes the schedule explicit and controllable
    (ref knob: HOROVOD_HIERARCHICAL_ALLREDUCE, common.h:122)."""
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError(f"hierarchical_allreduce supports SUM/AVERAGE, got {op}")

    def _one(t):
        ni = _axis_size_static(inner_axis)
        shape, dtype = t.shape, t.dtype
        flat = jnp.ravel(t)
        if prescale_factor != 1.0:
            flat = flat * jnp.asarray(prescale_factor, dtype)
        pad = (-flat.size) % ni
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros(pad, dtype)])
        shard = lax.psum_scatter(flat, inner_axis, tiled=True)
        shard = lax.psum(shard, outer_axis)
        full = invariant_allgather_shards(shard, inner_axis)
        if pad:
            full = full[:-pad]
        if op == ReduceOp.AVERAGE:
            full = full / (ni * _axis_size_static(outer_axis))
        if postscale_factor != 1.0:
            full = full * jnp.asarray(postscale_factor, full.dtype)
        return full.reshape(shape).astype(dtype)

    return jax.tree.map(_one, x)
