"""Fused Pallas optimizer kernels — the *update* side of the hot path.

The comm side of the gradient path is already fused
(ops/device.fused_allreduce buckets the pytree into few collectives);
this module fuses the other half.  A stock optax Adam step lowers to
~10 separate elementwise XLA ops — moment decay, moment update, two
bias corrections, rsqrt, divide, scale, apply — and on an HBM-bound
chip every one of them is a full read/write pass over every parameter.
ZeRO (Rajbhandari et al.) and LAMB (You et al.) both treat the
optimizer update as a first-class bandwidth target; these kernels do
the TPU-native version: one grid program reads a ``(grad, m, v)``
(+``param`` for weight decay) tile into VMEM, runs the ENTIRE Adam (or
SGD-momentum) recurrence on the VPU in f32, and writes ``(update, m,
v)`` back — one HBM pass per parameter, with the moment buffers
aliased in-place (``input_output_aliases``) so donated optimizer state
never double-buffers.

Exposed as optax-compatible ``GradientTransformation``s:

* :func:`fused_adam` — optax.adam/adamw semantics (bias-corrected
  moments, optional additive weight decay, schedule or float lr);
* :func:`fused_sgd` — optax.sgd semantics (momentum/nesterov trace).

Both compose with ``DistributedOptimizer``'s comm chain unchanged::

    opt = hvd.DistributedOptimizer(hvd.fused_adam(1e-3))
    updates, opt_state = opt.update(grads, opt_state, params)
    params = optax.apply_updates(params, updates)

Contract note: the optax ``update`` contract returns *updates* (the
delta), so ``apply_updates`` still costs one fused XLA add over the
params — the kernels collapse the ~10-op moment/correction chain into
one pass, and the delta-add is the single pass the optax interface
keeps.  The moment state round-trips HBM exactly once either way.

Eligibility + fallback: Mosaic tiles the trailing dim at 128 lanes
with a per-dtype sublane floor, so a leaf is kernel-eligible when its
flat size folds to ``[rows, 128]`` with a power-of-2 row tile >= the
floor (:func:`fused_update_eligible`).  Ineligible leaves (odd biases,
non-128 channel counts, sub-2-byte dtypes) take an XLA fallback with
the *same* f32-accumulated formulas, so the pytree never changes
semantics, only lowering.  The gate is platform-independent —
interpret mode has no alignment floor, but gating identically on CPU
means the CPU suite exercises the exact eligible/fallback split that
runs on hardware.  Kernels run under ``interpret=True`` off-TPU, so
tests compare the very same kernel code against optax
(tests/test_optim_kernels.py).
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..telemetry.compile_ledger import kernel_scope
from .pallas_kernels import _use_interpret, _vma_kw

__all__ = ["fused_adam", "fused_sgd", "fused_update_eligible",
           "sgd_leaf_update", "adam_leaf_update"]

_LANES = 128
# Per-dtype minimum sublane tile (see pallas_kernels._fit_block): Mosaic
# refuses smaller second-to-last dims on real TPU.
_SUBLANE = {4: 8, 2: 16, 1: 32}
# Row-tile upper bound: 512x128 f32 is 256 KiB per operand — 7 operands
# stay well under VMEM with double-buffering headroom.
_BLOCK_ROWS = 512


def _sublane_floor(*dtypes) -> int:
    return max(_SUBLANE.get(jnp.dtype(d).itemsize, 8) for d in dtypes)


def fused_update_eligible(leaf, *extra_dtypes) -> bool:
    """True when ``leaf`` can take the fused kernel: floating, >=2-byte
    dtype, flat size folding to ``[rows, 128]`` whose largest power-of-2
    row divisor clears the strictest sublane floor among the leaf's and
    ``extra_dtypes``' tiles.  Deliberately platform-independent (see
    module docstring) — CPU and TPU route identically."""
    dtype = jnp.dtype(leaf.dtype)
    if not jnp.issubdtype(dtype, jnp.floating) or dtype.itemsize < 2:
        return False
    for d in extra_dtypes:
        d = jnp.dtype(d)
        if not jnp.issubdtype(d, jnp.floating) or d.itemsize < 2:
            return False
    n = 1
    for s in leaf.shape:
        n *= int(s)
    if n == 0 or n % _LANES:
        return False
    rows = n // _LANES
    return (rows & -rows) >= _sublane_floor(leaf.dtype, *extra_dtypes)


def _row_block(rows: int) -> int:
    br = min(_BLOCK_ROWS, rows & -rows)
    return max(br, 1)


def _as2d(x):
    return x.reshape(x.size // _LANES, _LANES)


def _vma_align(*ops):
    """Promote operands to the union of their varying manual axes —
    replicated params meeting still-varying grads inside shard_map need
    matching vma before they share a kernel (same idiom as
    ops/conv_fused)."""
    from ..parallel.sharding import pcast_to_union

    return tuple(pcast_to_union(op, *ops) for op in ops)


# ---- Adam ----------------------------------------------------------------


def _adam_kernel(sc_ref, *refs, b1: float, b2: float, eps: float,
                 eps_root: float, wd: float):
    """One VMEM-resident tile: full Adam recurrence in f32 on the VPU.

    ``sc_ref`` (SMEM scalar prefetch): [lr, 1/(1-b1^t), 1/(1-b2^t)].
    With weight decay the param tile rides along (AdamW's additive
    term); without it the params are never even read.
    """
    if wd:
        p_ref, g_ref, m_ref, v_ref, d_ref, mo_ref, vo_ref = refs
    else:
        g_ref, m_ref, v_ref, d_ref, mo_ref, vo_ref = refs
    f32 = jnp.float32
    g = g_ref[...].astype(f32)
    m = b1 * m_ref[...].astype(f32) + (1.0 - b1) * g
    v = b2 * v_ref[...].astype(f32) + (1.0 - b2) * (g * g)
    u = (m * sc_ref[1]) / (jnp.sqrt(v * sc_ref[2] + eps_root) + eps)
    if wd:
        u = u + wd * p_ref[...].astype(f32)
    d_ref[...] = (-sc_ref[0] * u).astype(d_ref.dtype)
    mo_ref[...] = m.astype(mo_ref.dtype)
    vo_ref[...] = v.astype(vo_ref.dtype)


def _adam_leaf_fused(p, g, m, v, scalars, *, b1, b2, eps, eps_root, wd):
    """Single-HBM-pass Adam for one eligible leaf; returns (delta,
    m_new, v_new) in the leaf dtypes.  m/v alias their outputs."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    shape = g.shape
    ops = ((p, g, m, v) if wd else (g, m, v))
    ops = _vma_align(*ops)
    kw = _vma_kw(*ops)
    ops2d = tuple(_as2d(x) for x in ops)
    rows = ops2d[0].shape[0]
    br = _row_block(rows)
    spec = pl.BlockSpec((br, _LANES), lambda i, *_: (i, 0))
    n_in = len(ops2d)
    # Operand indices count the scalar-prefetch arg: scalars=0, then the
    # tensor operands; m and v are the last two inputs → alias onto the
    # m_new/v_new outputs (in-place moments under donation).
    aliases = {n_in - 1: 1, n_in: 2}
    with kernel_scope("fused_adam"):
        d, mo, vo = pl.pallas_call(
            functools.partial(_adam_kernel, b1=b1, b2=b2, eps=eps,
                              eps_root=eps_root, wd=wd),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(rows // br,),
                in_specs=[spec] * n_in, out_specs=[spec, spec, spec]),
            out_shape=(jax.ShapeDtypeStruct(ops2d[0].shape, p.dtype, **kw),
                       jax.ShapeDtypeStruct(ops2d[0].shape, m.dtype, **kw),
                       jax.ShapeDtypeStruct(ops2d[0].shape, v.dtype, **kw)),
            input_output_aliases=aliases,
            interpret=_use_interpret(),
        )(scalars, *ops2d)
    return d.reshape(shape), mo.reshape(shape), vo.reshape(shape)


def _adam_leaf_xla(p, g, m, v, scalars, *, b1, b2, eps, eps_root, wd):
    """Fallback for ineligible leaves — identical f32 math, XLA-fused."""
    f32 = jnp.float32
    g32 = g.astype(f32)
    m_new = b1 * m.astype(f32) + (1.0 - b1) * g32
    v_new = b2 * v.astype(f32) + (1.0 - b2) * (g32 * g32)
    u = (m_new * scalars[1]) / (jnp.sqrt(v_new * scalars[2] + eps_root)
                                + eps)
    if wd:
        u = u + wd * p.astype(f32)
    return ((-scalars[0] * u).astype(p.dtype), m_new.astype(m.dtype),
            v_new.astype(v.dtype))


def fused_adam(learning_rate, b1: float = 0.9, b2: float = 0.999,
               eps: float = 1e-8, eps_root: float = 0.0, *,
               weight_decay: float = 0.0,
               mu_dtype: Optional[Any] = None,
               use_kernels: bool = True):
    """optax.adam/adamw drop-in whose per-leaf update is one Pallas HBM
    pass (see module docstring).  ``learning_rate`` may be a float or an
    optax schedule (evaluated at the pre-increment step count, matching
    optax.scale_by_schedule).
    ``weight_decay`` > 0 gives adamw's additive decoupled decay.
    State is ``optax.ScaleByAdamState`` — checkpoints and
    ``DistributedOptimizer``/``MultiSteps`` wrappers see a stock shape.

    ``use_kernels=False`` forces the XLA fallback lowering for every
    leaf — same state tree, same f32 math, different lowering — which is
    what makes a fused-vs-unfused A/B (autotune's fused dimension)
    hot-swappable mid-run without re-initializing optimizer state.
    """
    import optax

    def init_fn(params):
        mu = jax.tree.map(
            lambda t: jnp.zeros_like(t, dtype=mu_dtype or t.dtype), params)
        nu = jax.tree.map(jnp.zeros_like, params)
        return optax.ScaleByAdamState(count=jnp.zeros([], jnp.int32),
                                      mu=mu, nu=nu)

    def update_fn(updates, state, params=None):
        if weight_decay and params is None:
            raise ValueError(
                "fused_adam(weight_decay=...) requires params: call "
                "update(grads, state, params)")
        count_inc = optax.safe_int32_increment(state.count)
        f32 = jnp.float32
        t = count_inc.astype(f32)
        # Schedules see the PRE-increment count (optax.scale_by_schedule
        # evaluates step_size_fn(state.count)); bias correction uses the
        # incremented count (optax.scale_by_adam) — match both exactly.
        lr = (learning_rate(state.count) if callable(learning_rate)
              else learning_rate)
        scalars = jnp.stack([
            jnp.asarray(lr, f32),
            1.0 / (1.0 - jnp.power(b1, t)),
            1.0 / (1.0 - jnp.power(b2, t))]).astype(f32)

        g_leaves, treedef = jax.tree.flatten(updates)
        m_leaves = treedef.flatten_up_to(state.mu)
        v_leaves = treedef.flatten_up_to(state.nu)
        p_leaves = (treedef.flatten_up_to(params) if params is not None
                    else g_leaves)

        out_d, out_m, out_v = [], [], []
        for p, g, m, v in zip(p_leaves, g_leaves, m_leaves, v_leaves):
            fn = (_adam_leaf_fused if use_kernels and
                  fused_update_eligible(g, p.dtype, m.dtype, v.dtype)
                  else _adam_leaf_xla)
            d, mn, vn = fn(p, g, m, v, scalars, b1=b1, b2=b2, eps=eps,
                           eps_root=eps_root, wd=weight_decay)
            out_d.append(d)
            out_m.append(mn)
            out_v.append(vn)
        return (jax.tree.unflatten(treedef, out_d),
                optax.ScaleByAdamState(
                    count=count_inc,
                    mu=jax.tree.unflatten(treedef, out_m),
                    nu=jax.tree.unflatten(treedef, out_v)))

    # Hyperparameter tag for the ZeRO router (ops/zero.py):
    # DistributedOptimizer(..., zero="states"/"params") shards this
    # update's math, so it must know the family + coefficients.
    update_fn._hvdt_optim_spec = {
        "kind": "adam", "learning_rate": learning_rate, "b1": b1,
        "b2": b2, "eps": eps, "eps_root": eps_root,
        "weight_decay": weight_decay, "use_kernels": use_kernels}
    return optax.GradientTransformation(init_fn, update_fn)


def adam_leaf_update(p, g, m, v, scalars, *, b1: float = 0.9,
                     b2: float = 0.999, eps: float = 1e-8,
                     eps_root: float = 0.0, weight_decay: float = 0.0,
                     use_kernels: bool = True):
    """Public per-leaf Adam update ``(delta, m_new, v_new)`` — the unit
    the overlap scheduler pipelines between bucket collectives
    (ops/overlap.exchange_and_update).  ``scalars`` is the
    ``[lr, 1/(1-b1^t), 1/(1-b2^t)]`` f32 stack (what ``fused_adam``
    builds per step); picks the single-HBM-pass Pallas kernel when the
    leaf is tile-eligible, the identical-math XLA fallback otherwise."""
    fn = (_adam_leaf_fused if use_kernels
          and fused_update_eligible(g, p.dtype, m.dtype, v.dtype)
          else _adam_leaf_xla)
    return fn(p, g, m, v, scalars, b1=b1, b2=b2, eps=eps,
              eps_root=eps_root, wd=weight_decay)


# ---- SGD (momentum) ------------------------------------------------------


def _sgd_kernel(sc_ref, g_ref, m_ref, d_ref, mo_ref, *, momentum: float,
                nesterov: bool):
    """optax.trace recurrence in one tile pass: m = g + momentum*m;
    update = g + momentum*m (nesterov) or m."""
    f32 = jnp.float32
    g = g_ref[...].astype(f32)
    m = g + momentum * m_ref[...].astype(f32)
    u = g + momentum * m if nesterov else m
    d_ref[...] = (-sc_ref[0] * u).astype(d_ref.dtype)
    mo_ref[...] = m.astype(mo_ref.dtype)


def _sgd_leaf_fused(g, m, scalars, *, momentum, nesterov):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    shape = g.shape
    g, m = _vma_align(g, m)
    kw = _vma_kw(g, m)
    g2, m2 = _as2d(g), _as2d(m)
    rows = g2.shape[0]
    br = _row_block(rows)
    spec = pl.BlockSpec((br, _LANES), lambda i, *_: (i, 0))
    with kernel_scope("fused_sgd"):
        d, mo = pl.pallas_call(
            functools.partial(_sgd_kernel, momentum=momentum,
                              nesterov=nesterov),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(rows // br,),
                in_specs=[spec, spec], out_specs=[spec, spec]),
            out_shape=(jax.ShapeDtypeStruct(g2.shape, g.dtype, **kw),
                       jax.ShapeDtypeStruct(g2.shape, m.dtype, **kw)),
            input_output_aliases={2: 1},     # m (after scalars, g) → m_new
            interpret=_use_interpret(),
        )(scalars, g2, m2)
    return d.reshape(shape), mo.reshape(shape)


def _sgd_leaf_xla(g, m, scalars, *, momentum, nesterov):
    f32 = jnp.float32
    g32 = g.astype(f32)
    m_new = g32 + momentum * m.astype(f32)
    u = g32 + momentum * m_new if nesterov else m_new
    return (-scalars[0] * u).astype(g.dtype), m_new.astype(m.dtype)


def sgd_leaf_update(g, m, scalars, *, momentum: float,
                    nesterov: bool = False, use_kernels: bool = True):
    """Public per-leaf SGD-momentum update ``(delta, new_trace)`` — the
    unit the overlap scheduler pipelines between bucket collectives
    (ops/overlap.exchange_and_update / pipelined_sgd).  ``scalars`` is
    the 1-element f32 ``[lr]`` stack; picks the single-HBM-pass Pallas
    kernel when the leaf is tile-eligible, the identical-math XLA
    fallback otherwise."""
    fn = (_sgd_leaf_fused
          if use_kernels and fused_update_eligible(g, m.dtype)
          else _sgd_leaf_xla)
    return fn(g, m, scalars, momentum=momentum, nesterov=nesterov)


def fused_sgd(learning_rate, momentum: float = 0.0,
              nesterov: bool = False, *, use_kernels: bool = True):
    """optax.sgd drop-in; with ``momentum`` the trace update runs as one
    Pallas HBM pass per eligible leaf.  Without momentum there is no
    state and the update is the single XLA scale it always was (nothing
    to fuse).  State is ``optax.TraceState``.  Schedules need a step
    count the stock TraceState doesn't carry — pass a float (or use
    :func:`fused_adam`, which supports schedules).
    ``use_kernels=False``: XLA fallback lowering for every leaf, same
    state tree — the hot-swappable unfused A/B leg (see fused_adam)."""
    import optax

    if callable(learning_rate):
        raise ValueError(
            "fused_sgd takes a float learning_rate (TraceState carries "
            "no step count for a schedule); use fused_adam for "
            "schedule support")
    if not momentum:
        def init_plain(params):
            del params
            return optax.EmptyState()

        def update_plain(updates, state, params=None):
            del params
            return (jax.tree.map(
                lambda g: (-learning_rate
                           * g.astype(jnp.float32)).astype(g.dtype),
                updates), state)

        update_plain._hvdt_optim_spec = {
            "kind": "sgd", "learning_rate": learning_rate,
            "momentum": 0.0, "nesterov": False,
            "use_kernels": use_kernels}
        return optax.GradientTransformation(init_plain, update_plain)

    def init_fn(params):
        return optax.TraceState(trace=jax.tree.map(jnp.zeros_like, params))

    def update_fn(updates, state, params=None):
        del params
        scalars = jnp.stack([jnp.asarray(learning_rate, jnp.float32)])

        g_leaves, treedef = jax.tree.flatten(updates)
        m_leaves = treedef.flatten_up_to(state.trace)
        out_d, out_m = [], []
        for g, m in zip(g_leaves, m_leaves):
            fn = (_sgd_leaf_fused
                  if use_kernels and fused_update_eligible(g, m.dtype)
                  else _sgd_leaf_xla)
            d, mn = fn(g, m, scalars, momentum=momentum, nesterov=nesterov)
            out_d.append(d)
            out_m.append(mn)
        return (jax.tree.unflatten(treedef, out_d),
                optax.TraceState(trace=jax.tree.unflatten(treedef, out_m)))

    # ZeRO router tag (see fused_adam).
    update_fn._hvdt_optim_spec = {
        "kind": "sgd", "learning_rate": learning_rate,
        "momentum": momentum, "nesterov": nesterov,
        "use_kernels": use_kernels}
    return optax.GradientTransformation(init_fn, update_fn)
