"""DistributedOptimizer — the gradient-averaging wrapper.

TPU-native re-conception of the reference's optimizer wrappers
(ref: torch/optimizer.py — _DistributedOptimizer grad-hooks :131-253,
synchronize :255-302, factory :516-605; tensorflow/__init__.py:627
DistributedOptimizer, _DistributedGradientTape :758-842;
gradient_aggregation*.py backward_passes_per_step).

Design translation: the reference hooks per-parameter gradient-ready events
and enqueues named async allreduces that the background thread fuses.  Under
jit there are no per-tensor ready events — the whole gradient pytree is
materialized by ``jax.grad`` — so the idiomatic equivalent is an optax
``GradientTransformation`` that buckets the gradient pytree into fused
collectives (ops/device.fused_allreduce) as the FIRST link of the optimizer
chain.  XLA then overlaps the bucketed all-reduces with the parameter
update and neighbouring compute (the async-dispatch analog of hook-driven
overlap).

``backward_passes_per_step`` maps to local gradient accumulation with the
collective executed only on boundary steps (ref:
gradient_aggregation.py) — expressed with ``optax.MultiSteps`` around the
communicating chain.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from .common.types import ReduceOp
from .ops import device as dev
from .ops.compression import Compression, Compressor

__all__ = ["DistributedOptimizer", "allreduce_gradients",
           "DistributedGradientTransformation", "microbatch_gradients"]


def microbatch_gradients(grad_fn, params, batch, num_microbatches: int,
                         axis="dp", op: ReduceOp = ReduceOp.AVERAGE,
                         compression=None,
                         threshold_bytes: Optional[int] = None):
    """Accumulate gradients over micro-batches, then communicate ONCE.

    The TPU-idiomatic equivalent of the reference's
    ``backward_passes_per_step`` bandwidth optimization
    (ref: gradient_aggregation.py — skip allreduce on non-boundary
    backward passes): instead of conditional collectives across optimizer
    steps, micro-batches are scanned *inside* one jitted step and a single
    fused collective reduces the accumulated gradient.

    Args:
      grad_fn: ``grad_fn(params, microbatch) -> grads`` pytree.
      batch: pytree whose leaves have a leading axis divisible by
        ``num_microbatches``; reshaped to (k, b/k, ...) and scanned.

    Returns the communicated (averaged by default) gradient pytree.
    """
    import jax
    import jax.numpy as jnp

    def reshape(leaf):
        return leaf.reshape((num_microbatches, -1) + leaf.shape[1:])

    micro = jax.tree.map(reshape, batch)

    # Accumulate float gradients in f32 regardless of the compute dtype:
    # summing k bf16 micro-gradients in bf16 loses low bits every add
    # (8 mantissa bits — by 8 microbatches the accumulated drift is
    # visible in the loss trajectory; tests/test_zero.py pins the
    # regression).  One widen per micro-step, one cast back at the end.
    def acc_dtype(t):
        return (jnp.float32
                if jnp.issubdtype(jnp.result_type(t), jnp.floating)
                else jnp.result_type(t))

    def body(acc, mb):
        g = grad_fn(params, mb)
        return jax.tree.map(
            lambda a, gg: a + gg.astype(a.dtype), acc, g), None

    zero = jax.tree.map(
        lambda t: jnp.zeros(t.shape, acc_dtype(t)), params)
    total, _ = jax.lax.scan(body, zero, micro)
    total = jax.tree.map(
        lambda t, p: (t / num_microbatches).astype(
            jnp.result_type(p)), total, params)
    from .ops.compression import Compression as _C

    return allreduce_gradients(total, axis=axis, op=op,
                               compression=compression or _C.none,
                               threshold_bytes=threshold_bytes)


def pvary_tree(tree, axis="dp"):
    """Mark a replicated pytree as per-rank *varying* over ``axis``.

    Differentiating w.r.t. unvarying params under shard_map inserts the
    gradient psum automatically — which destroys the per-rank gradients
    Adasum (and custom reductions) need.  Differentiate w.r.t. the
    *varying* params (pcast applied OUTSIDE the loss closure — its
    transpose is itself a psum)::

        loss, grads = jax.value_and_grad(loss_fn)(
            hvd.optimizer.pvary_tree(params, "dp"))

    then pass the varying grads to DistributedOptimizer(op=hvd.Adasum).
    """
    import jax
    from jax import lax

    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    return jax.tree.map(lambda t: lax.pcast(t, axes, to="varying"), tree)


def _axis_bound(axis) -> bool:
    """True when ``axis`` is a bound manual mesh axis (i.e. we are inside a
    shard_map body).  Under plain auto-sharded jit/pjit there are no bound
    axes — gradients there are already globally correct and the comm link
    must be the identity."""
    try:
        dev._axis_size_static(axis)
        return True
    except NameError:
        return False


def allreduce_gradients(grads, axis="dp", op: ReduceOp = ReduceOp.AVERAGE,
                        compression: Compressor = Compression.none,
                        threshold_bytes: Optional[int] = None,
                        prescale_factor: float = 1.0,
                        postscale_factor: float = 1.0,
                        _exchange: Optional[Any] = None):
    """Functional gradient allreduce for custom train steps.

    The building block DistributedOptimizer uses; exposed for users who
    write their own update loops (the analog of calling hvd.allreduce on
    each grad, but bucketed/fused).

    Gradient-aware semantics: "the update uses the average (or sum) of
    per-rank gradients" in every regime —

    * shard_map, grads varying over ``axis`` (params were per-shard /
      pvary'd): fused psum collectives, ÷n for Average.
    * shard_map, grads UNVARYING over ``axis``: modern JAX AD has already
      cross-shard-summed the cotangent of replicated params (see
      ops.device.is_varying), so Average is ÷n and Sum is the identity —
      no collective issued at all.
    * plain auto-sharded jit (no bound axis): gradients are already global;
      identity.
    """
    wire_dtype = compression.wire_dtype
    if wire_dtype == "bfloat16":
        wire_dtype = jnp.bfloat16
    if not _axis_bound(axis):
        return grads

    import jax

    leaves, treedef = jax.tree.flatten(grads)
    if not leaves:
        return grads
    n = 1
    for a in ((axis,) if isinstance(axis, str) else tuple(axis)):
        n *= dev._axis_size_static(a)

    varying_idx = [i for i, l in enumerate(leaves) if dev.is_varying(l, axis)]
    unvarying_idx = [i for i in range(len(leaves)) if i not in set(varying_idx)]

    out = list(leaves)
    if unvarying_idx:
        if op == ReduceOp.ADASUM:
            raise ValueError(
                "Adasum needs per-rank gradients, but these gradients are "
                "unvarying over the mesh axis (already summed by AD). "
                "Compute grads w.r.t. pvary'd params, e.g. "
                "jax.lax.pcast(params, to='varying').")
        scale = prescale_factor * postscale_factor
        if op == ReduceOp.AVERAGE:
            scale = scale / n
        elif op != ReduceOp.SUM:
            raise ValueError(f"Unsupported gradient reduce op: {op}")
        for i in unvarying_idx:
            out[i] = out[i] * scale if scale != 1.0 else out[i]
    if varying_idx:
        # Overlap routing (ops/overlap.py): HVDT_OVERLAP=on swaps the
        # monolithic fused_allreduce for the dependency-ordered bucket
        # schedule; off/unset returns fused_allreduce ITSELF (identity
        # contract — the pre-existing code object, zero wrappers).
        # ``_exchange`` is the ZeRO hook: the grads-stage comm
        # transformation passes ops.zero.rs_exchange here so the same
        # gradient-aware varying logic drives the reduce-scatter wire.
        from .ops.overlap import exchange_fn

        reduced = (_exchange or exchange_fn())(
            [leaves[i] for i in varying_idx], axis=axis, op=op,
            threshold_bytes=threshold_bytes,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor, wire_dtype=wire_dtype)
        for i, v in zip(varying_idx, reduced):
            out[i] = v
    # NOTE: reduced outputs are intentionally left unvarying (replicated) —
    # that is their true type after a psum, it lets users keep P() out_specs
    # for params/opt state, and it keeps optax.MultiSteps' internal lax.cond
    # type-stable.
    return jax.tree.unflatten(treedef, out)


def DistributedGradientTransformation(
        axis="dp", op: ReduceOp = ReduceOp.AVERAGE,
        compression: Compressor = Compression.none,
        threshold_bytes: Optional[int] = None,
        prescale_factor: float = 1.0,
        postscale_factor: float = 1.0,
        zero: Optional[Any] = None):
    """An optax transformation that allreduces incoming gradients.

    ``zero`` (default: the ``HVDT_ZERO`` env stage) at ``grads`` or
    beyond swaps the fused-allreduce wire for the explicit
    reduce-scatter + invariant-allgather split (ops/zero.rs_exchange —
    same reduced values, deferrable allgather); unset keeps the
    pre-existing replicated exchange as the identical code objects.
    """
    import optax

    from .ops import zero as _zero

    stage = _zero.resolve_stage(zero)
    exchange = None if stage is None else _zero.rs_exchange

    def init_fn(params):
        del params
        return optax.EmptyState()

    def update_fn(updates, state, params=None):
        del params
        # Collectives and the packing, scaling and unpacking around them
        # under one name; the per-bucket scopes nest inside it.
        with jax.named_scope("hvdt.exchange"):
            updates = allreduce_gradients(
                updates, axis=axis, op=op, compression=compression,
                threshold_bytes=threshold_bytes,
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor,
                _exchange=exchange)
        return updates, state

    return optax.GradientTransformation(init_fn, update_fn)


def _scoped_update(optimizer):
    """``optimizer`` with its ``update`` traced under ``hvdt.optimizer``;
    ``init`` and the state pytree are the wrapped transformation's own."""
    import optax

    inner = optax.with_extra_args_support(optimizer)

    def update_fn(updates, state, params=None, **extra_args):
        with jax.named_scope("hvdt.optimizer"):
            return inner.update(updates, state, params, **extra_args)

    return optax.GradientTransformationExtraArgs(inner.init, update_fn)


def DistributedOptimizer(optimizer,
                         *,
                         axis="dp",
                         op: ReduceOp = ReduceOp.AVERAGE,
                         compression: Optional[Compressor] = None,
                         backward_passes_per_step: int = 1,
                         threshold_bytes: Optional[int] = None,
                         prescale_factor: float = 1.0,
                         postscale_factor: float = 1.0,
                         zero: Optional[Any] = None,
                         pipeline: Optional[str] = None,
                         expert: Optional[str] = None):
    """Wrap an optax optimizer so gradients are averaged across the mesh
    axis before the update (ref: torch/optimizer.py:516 DistributedOptimizer
    factory; same call-shape philosophy: wrap and use as usual).

    Use inside a shard_map/pjit step function where ``axis`` is a bound mesh
    axis name::

        opt = hvd.DistributedOptimizer(optax.adam(1e-3))
        updates, opt_state = opt.update(grads, opt_state, params)

    The update side composes with the fused Pallas optimizer kernels
    unchanged — ``hvd.DistributedOptimizer(hvd.fused_adam(1e-3))`` runs
    the comm chain into a single-HBM-pass Adam update
    (ops/optim_kernels.py; ineligible leaves fall back to identical XLA
    math automatically).

    Args:
      optimizer: the optax GradientTransformation to wrap.
      axis: mesh axis to reduce over (data-parallel axis).
      op: Average (default), Sum, or Adasum.
      compression: Compression.none / .bf16 / .fp16 — wire dtype for the
        fused collectives — or Compression.int8 for the block-scaled
        quantized wire (horovod_tpu/quant; pair with
        ``hvd.quant.with_error_feedback`` for f32-parity convergence).
        None (default) resolves from the environment
        (``HVDT_COMPRESSION`` / ``HVDT_QUANT`` — Compression.from_env).
      backward_passes_per_step: accumulate this many micro-batch gradients
        locally between collectives (ref: gradient_aggregation.py).
      zero: ZeRO state-sharding stage (ops/zero.py) — ``"grads"`` (the
        reduce-scatter wire, any optax optimizer), ``"states"``
        (sharded moments + shard-local fused update + delta allgather;
        requires ``hvd.fused_adam``/``hvd.fused_sgd``), ``"params"``
        (params sharded between steps), a ``zero.ZeroSpec`` for explicit
        ``num_shards``/threshold, or None (default) to read
        ``HVDT_ZERO``.  Unset/off keeps the replicated chain as the
        identical pre-existing code objects (identity-tested).
      pipeline: mesh axis name the step's parameters are PIPELINE-sharded
        over (parallel.pipeline_1f1b stages).  A sharded axis is the
        opposite of a reduce axis — every rank owns different stage
        params, so their gradients must stay per-rank.  Declaring it
        here is the 4D-mesh contract: the wrapper refuses an ``axis``
        that overlaps it (reducing over ``pp`` would average unrelated
        stages' gradients into garbage), and ZeRO keeps sharding state
        WITHIN the remaining ``axis`` group only.
      expert: mesh axis name expert parameters are sharded over
        (parallel.moe_dispatch_combine).  Same contract as ``pipeline``:
        per-rank experts, per-rank gradients, excluded from the reduce
        group.
    """
    import optax

    from .ops import zero as _zero

    reduce_axes = (axis,) if isinstance(axis, str) else tuple(axis)
    for kind, sharded in (("pipeline", pipeline), ("expert", expert)):
        if sharded is not None and sharded in reduce_axes:
            raise ValueError(
                f"{kind}={sharded!r} names a parameter-SHARDED mesh axis "
                f"but axis={axis!r} would reduce gradients over it — "
                f"every {sharded} rank owns different parameters, so "
                "averaging across it destroys them.  Drop it from the "
                "reduce group (ZeRO then shards state within the "
                "remaining data-parallel group).")
    _stage = _zero.resolve_stage(zero)
    if compression is None:
        compression = Compression.from_env()
    if _stage in ("states", "params"):
        zspec = zero if isinstance(zero, _zero.ZeroSpec) else None
        return _zero.zero_from_optimizer(
            optimizer, stage=_stage, axis=axis, op=op,
            num_shards=(zspec.num_shards if zspec else None),
            threshold_bytes=(threshold_bytes if threshold_bytes is not None
                             else (zspec.threshold_bytes if zspec
                                   else None)),
            wire_dtype=compression.wire_dtype,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor)
    from .telemetry.instrument import get_recorder

    _rec = get_recorder()
    if _rec is not None:
        # Construction-time config record: the jit-traced update can't
        # report per-step from inside the program, but which wire format
        # / reduce op the job trains with is the label every collective
        # series gets joined against.
        _rec.registry.counter(
            "hvdt_distributed_optimizer_builds_total",
            "DistributedOptimizer constructions, labelled op/compression"
        ).inc(op=ReduceOp(op).name.lower(),
              compression=getattr(compression, "__name__", "none"),
              backward_passes=str(backward_passes_per_step),
              pipeline=pipeline or "off", expert=expert or "off")
    comm = DistributedGradientTransformation(
        axis=axis, op=op, compression=compression,
        threshold_bytes=threshold_bytes, prescale_factor=prescale_factor,
        postscale_factor=postscale_factor, zero=_stage)
    optimizer = _scoped_update(optimizer)
    if backward_passes_per_step > 1:
        # Communication precedes accumulation so every value MultiSteps
        # holds across its internal lax.cond is replicated (type-stable
        # under JAX's varying-manual-axes tracking).  To also SKIP
        # collectives on non-boundary micro-steps — the reference's
        # bandwidth optimization (gradient_aggregation.py) — use the
        # TPU-idiomatic microbatch_gradients() inside one jitted step,
        # which issues a single fused collective per k micro-batches.
        return optax.chain(
            comm,
            optax.MultiSteps(optimizer,
                             every_k_schedule=backward_passes_per_step))
    return optax.chain(comm, optimizer)
