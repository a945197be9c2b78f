"""Expert layers: a dropless top-k layer over the experts one chip holds,
and a capacity-factor top-k MoE with alltoall token routing.

:func:`moe_held_experts` is the layer a model calls where this device holds
a range of a layer's experts and sees all of its own tokens (one chip's
share of an expert-parallel layer, or every expert of a small model): it
routes over ALL routed experts, keeps the picks that land on held ones,
sorts them by expert and runs grouped matrix products over those rows.  No
capacity, no dropped pick.  :func:`moe_dispatch_combine` below is the older
exchange across an ``ep`` axis, with a static capacity that drops.


The reference exposes the raw alltoall primitive that makes user-level MoE
possible (ref: operations.cc:1642-1725, ops/collective_operations.h:195
AlltoallOp) but ships no EP layer (SURVEY.md §2.7).  Here the full dispatch
→ expert → combine path is provided, TPU-style: static capacity (no dynamic
shapes for XLA), ``lax.all_to_all`` over the ``ep`` mesh axis.

The token exchange rides the transport-policy layer
(horovod_tpu/transport): an ``HVDT_TRANSPORT=ep:ring:int8:8M`` entry puts
the dispatch/combine payloads on the block-scaled int8 wire (quant/kernels
— real int8 bytes plus f32 block scales on the wire, f32 math on both
ends), exactly like the gradient allreduce's per-axis wire override.
Both alltoalls are booked against the trace-time telemetry and flight
recorder (ops/device.fused_allreduce idiom), so ``hvdt_collective_*``
series and desync forensics cover expert routing with no extra wiring.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..ops.device import _axis_size_static

__all__ = ["moe_dispatch_combine", "moe_held_experts", "moe_route", "MoEAux",
           "moe_capacity", "report_moe_aux", "ROUTE_SAVED"]


class MoEAux(NamedTuple):
    load_balance_loss: jax.Array   # switch-transformer aux loss (scalar)
    dropped_fraction: jax.Array    # fraction of tokens over capacity (scalar)
    # The dropless layer's load (None from the capacity dispatcher):
    held_rows: Optional[jax.Array] = None         # picks that landed here
    max_expert_rows: Optional[jax.Array] = None   # the fullest held expert's


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "")
    try:
        return float(raw) if raw else default
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name, "")
    try:
        return int(raw) if raw else default
    except ValueError:
        return default


def moe_capacity(tokens_per_rank: int, num_experts: int, *,
                 top_k: int = 1, capacity_factor: float = 1.25) -> int:
    """Per-expert dispatch slots: ``ceil(T·k/E · factor)``, floor 1.

    The static-shape contract every tensor in the dispatch path is sized
    by (GShard's expert capacity) — XLA never sees a data-dependent
    shape; tokens beyond it are dropped (residual passthrough)."""
    want = tokens_per_rank * top_k * capacity_factor
    return max(1, int(-(-want // num_experts)))


# ---------------------------------------------------------------------------
# The dropless layer over held experts.
# ---------------------------------------------------------------------------


# The layer's two moves: tokens' rows into the buffer sorted by expert, and
# the experts' rows back to their tokens.  Both are gathers, and each is the
# other's transpose (``order`` is a permutation of the picks and ``inverse``
# its inverse), which is what their custom VJPs say: the backward holds no
# scatter-add.  ``held`` [T, k] says which picks landed here: exactly those
# sit in the rows the grouped products compute, so what the products leave
# in the other rows is selected away where rows enter a token's sum.  Their
# cost is the buffer's (all T x k rows move, whatever landed) and so does
# not change with the routing: measured on the v5e at 131,072 rows of 2048
# (PERF.md, PR 31 and PR 40), a gather from the [T, D] activations 0.83 ms;
# the rows back to their tokens 1.42 ms as one Mosaic call that DMAs the
# runs of the sort a tile of tokens needs and sums them in VMEM, 5.2 ms as
# XLA's gather from the [T x k, D] buffer and the sum over its copy (1.76
# against 9.7 at top-10, where XLA also lays the copy out again); a
# scatter-add that walks only the rows that landed 4.2 ms for 16,384 rows
# and more or less with the load.


def _sum_held_rows_xla(rows, inverse, held):
    """:func:`_sum_held_rows` in plain ``jnp``: XLA writes the gathered
    ``[T * k, D]`` rows, reads them back for the sum and, where k does not
    fill a sublane tile (top-10), lays them out again between the two."""
    t, k = held.shape
    picks = jnp.where(held[:, :, None], rows[inverse].reshape(t, k, -1), 0)
    return picks.sum(1, dtype=jnp.float32).astype(rows.dtype)


def _sum_held_rows(rows, inverse, held, segment, segments):
    """[T * k, D] -> [T, D]: each token the float32 sum of its held picks'
    rows; a pick's row is ``rows[inverse[pick]]``.  One Mosaic call
    (``ops.pallas_kernels.moe_sum_rows``, which reads the rows by the runs
    of the sort: ``segment`` [T * k] is a pick's key, one of ``segments``)
    where the shapes have blocks for it, the tokens whole programs of 1,024
    and a row whole lanes (training and long prefills), XLA's form
    elsewhere (a decode step, short prefills, toy widths): the same sum of
    the same rows either way."""
    from ..ops.pallas_kernels import _moe_sum_rows_blocks, moe_sum_rows

    if _moe_sum_rows_blocks(*held.shape, rows.shape[1], segments,
                            rows.dtype) is None:
        return _sum_held_rows_xla(rows, inverse, held)
    return moe_sum_rows(rows, inverse, held, segment, segments=segments)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rows_of_tokens(x, order, inverse, held, segment, segments):
    """x [T, D] -> [T * k, D]: row r is the token of pick ``order[r]``."""
    return x[order // held.shape[1]]


def _rows_of_tokens_fwd(x, order, inverse, held, segment, segments):
    return (_rows_of_tokens(x, order, inverse, held, segment, segments),
            (inverse, held, segment))


def _rows_of_tokens_bwd(segments, res, g):
    return (_sum_held_rows(g, *res, segments),) + (None,) * 4


_rows_of_tokens.defvjp(_rows_of_tokens_fwd, _rows_of_tokens_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _tokens_of_rows(rows, order, inverse, held, segment, segments):
    """rows [T * k, D] -> [T, D]: each token the sum of its held picks'
    rows."""
    return _sum_held_rows(rows, inverse, held, segment, segments)


def _tokens_of_rows_fwd(rows, order, inverse, held, segment, segments):
    return (_tokens_of_rows(rows, order, inverse, held, segment, segments),
            (order, held))


def _tokens_of_rows_bwd(segments, res, g):
    order, held = res
    # The rows past those that landed get their token's too: no product
    # reads them.
    return (g[order // held.shape[1]],) + (None,) * 4


_tokens_of_rows.defvjp(_tokens_of_rows_fwd, _tokens_of_rows_bwd)


# What the route hands on, under one ``checkpoint_name``: a layer's
# ``jax.checkpoint`` that saves this name (``models/transformer._layer_fn``)
# keeps the picks, the picked scores, the sort and the rows' weights from
# its forward, and its backward body holds no top-k, no sort and no router
# product.  Seven [T * k] vectors and a count an expert, about 25 bytes a
# pick (4 MB at 163,840 picks) against the layer's [T, D] input that a
# checkpoint saves anyway; every one is a residual of the backward (the
# moves' rules, the grouped products' transposes, ``mid``'s weight, the
# router's own cotangent), so recomputing them bought nothing.  Outside a
# checkpoint, and under one with another policy, the name is an identity.
ROUTE_SAVED = "hvdt_moe_route"


def _saved(x):
    return checkpoint_name(x, ROUTE_SAVED)


def _scores(z, score: str):
    if score == "sigmoid":
        return jax.nn.sigmoid(z)
    if score == "softmax":
        return jax.nn.softmax(z, axis=-1)
    raise ValueError(f"unknown router score {score!r} "
                     "(expected 'sigmoid' or 'softmax')")


def _weights_of_picked(picked, normalize: bool, scale: float,
                       eps: float = 0.0):
    if normalize:
        total = picked.sum(-1, keepdims=True)
        picked = picked / (total + eps if eps else
                           jnp.maximum(total, 1e-20))
    return picked * scale


def _picked_and_experts(scores, select_bias, top_k):
    """A row's scores at its picks and the picks [T, k]: the k largest
    scores, or with ``select_bias`` [E] the k largest of ``scores +
    select_bias``, whose picked scores are still the scores' own (the bias
    chooses and does not weigh)."""
    if select_bias is None:
        return lax.top_k(scores, top_k)
    # A scope of its own: the compiled step says which form it routes by.
    with jax.named_scope("hvdt.moe.route.select_bias"):
        experts = lax.top_k(scores + lax.stop_gradient(select_bias),
                            top_k)[1]
    lanes = jnp.arange(scores.shape[-1], dtype=experts.dtype)
    # [T, E] -> [T, k] by comparison, one fused pass: no gather.
    return jnp.sum(jnp.where(experts[..., None] == lanes,
                             scores[:, None, :], 0.0), axis=-1), experts


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _picks_of_logits(z, select_bias, score, top_k, normalize, scale, eps):
    """Logits [T, E] -> (picked experts [T, k], their weights [T, k]) where
    the cotangent needs a token's k picked scores and no other: ``sigmoid``
    scores, or ``softmax`` scores divided by their sum over the picks.
    ``select_bias`` [E] or None: added to the scores for the choice alone;
    no cotangent reaches it."""
    return _picks_of_logits_fwd(z, select_bias, score, top_k, normalize,
                                scale, eps)[0]


def _picks_of_logits_fwd(z, select_bias, score, top_k, normalize, scale,
                         eps):
    picked, experts = map(_saved, _picked_and_experts(
        _scores(z, score), select_bias, top_k))
    lanes = jnp.arange(z.shape[-1], dtype=experts.dtype)
    return ((experts, _weights_of_picked(picked, normalize, scale, eps)),
            (experts, picked, lanes,
             None if select_bias is None else jnp.zeros_like(select_bias)))


def _picks_of_logits_bwd(score, top_k, normalize, scale, eps, res, g):
    experts, picked, lanes, no_bias_cotangent = res
    _, weights_vjp = jax.vjp(
        lambda p: _weights_of_picked(p, normalize, scale, eps), picked)
    d_picked, = weights_vjp(g[1])
    if score == "sigmoid":
        dz = d_picked * picked * (1.0 - picked)
    else:
        # softmax: dz_e = s_e (ds_e - sum_j ds_j s_j).  The weights are
        # homogeneous of degree 0 in the picked scores, so the sum is 0
        # (the 1e-20 floor never binds: the largest of E softmax scores is
        # at least 1 / E) and dz is 0 off the picks.
        dz = d_picked * picked
    # [T, k] -> [T, E] by comparison, one fused pass: no scatter.
    return (jnp.sum(jnp.where(experts[..., None] == lanes, dz[..., None],
                              0.0), axis=1), no_bias_cotangent)


_picks_of_logits.defvjp(_picks_of_logits_fwd, _picks_of_logits_bwd)


def _count_route(select_bias) -> None:
    """One traced route, by what chose its picks (trace time, as the
    collectives' counters: a program's count, not a step's)."""
    from ..telemetry import instrument as _ti

    rec = _ti.get_recorder()
    if rec is not None:
        rec.registry.counter(
            "hvdt_moe_routes_total",
            "Expert-layer routes traced, labelled by what chose the picks: "
            "select=score (the scores themselves) or score_plus_bias (the "
            "scores plus a selection bias that does not weigh)").inc(
            1.0, select="score" if select_bias is None
            else "score_plus_bias")


def moe_route(x: jax.Array, w_router: jax.Array, *, top_k: int,
              score: str = "sigmoid", normalize: bool = True,
              scale: float = 1.0, select_bias: Optional[jax.Array] = None,
              normalize_eps: float = 0.0):
    """Scores over every routed expert and each token's picks, in float32
    (the router's product at ``HIGHEST`` precision: a pick is a
    comparison of scores, and a bf16 product decides thousands of them
    otherwise).  ``x`` [T, D], ``w_router`` [D, E] -> (scores [T, E],
    picked experts [T, k], their weights [T, k]).  ``score`` is
    ``"sigmoid"`` or ``"softmax"``; ``normalize`` divides the picked scores
    by their sum (plus ``normalize_eps`` where that is set; else the sum is
    floored at 1e-20); ``scale`` multiplies the weights.  ``select_bias``
    [E], float32: the picks are the k largest of ``scores + select_bias``
    and the weights are still made of the scores at them, without it; it
    is a constant of the step (``stop_gradient``: its gradient is exactly
    zero) that a balancing rule outside the loss would move.

    The picks and the picked scores carry :data:`ROUTE_SAVED`.  The
    weights' cotangent reaches the logits from the k picked scores of a
    token alone (:func:`_picks_of_logits`), except for ``softmax`` scores
    whose weights are not homogeneous in the picked scores (not normalised
    over the picks, or normalised with a ``normalize_eps``), whose rule
    reads the whole row: there it is autodiff's, through the picked scores
    gathered by the named picks (``lax.top_k``'s own rule gathers by an
    index nothing names)."""
    _count_route(select_bias)
    if select_bias is not None:
        select_bias = select_bias.astype(jnp.float32)
    z = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                precision=lax.Precision.HIGHEST)
    scores = _scores(z, score)
    if score == "softmax" and (not normalize or normalize_eps):
        chosen_by = lax.stop_gradient(scores)
        if select_bias is not None:
            chosen_by = chosen_by + lax.stop_gradient(select_bias)
        experts = _saved(lax.top_k(chosen_by, top_k)[1])
        picked = _saved(jnp.take_along_axis(scores, experts, axis=-1))
        return scores, experts, _weights_of_picked(
            picked, normalize, scale, normalize_eps)
    return (scores,) + _picks_of_logits(
        z, select_bias, score, top_k, normalize, scale, float(normalize_eps))


def moe_held_experts(x: jax.Array, w_router: jax.Array, w_up: jax.Array,
                     w_down: jax.Array, w_gate: Optional[jax.Array] = None,
                     *, top_k: int, experts_first: int = 0,
                     score: str = "sigmoid", normalize: bool = True,
                     scale: float = 1.0,
                     select_bias: Optional[jax.Array] = None,
                     normalize_eps: float = 0.0,
                     shared_fn: Optional[Callable[[jax.Array], jax.Array]]
                     = None) -> Tuple[jax.Array, MoEAux]:
    """A top-k expert layer over the experts held here, without drops.

    ``x`` [T, D] are this device's tokens; ``w_router`` [D, E] scores ALL
    ``E`` routed experts; ``w_up`` / ``w_gate`` [H, D, F] and ``w_down``
    [H, F, D] are the ``H`` held ones, experts ``experts_first ..
    experts_first + H - 1`` of the layer (``w_gate`` None: ``silu(x W_up)
    W_down``, else SwiGLU ``(silu(x W_gate) * x W_up) W_down``);
    ``select_bias`` [E] and ``normalize_eps`` are :func:`moe_route`'s (a
    bias that chooses the picks and does not weigh them).  Returns
    ``sum over a token's picks that are held of weight * expert(x)`` plus
    ``shared_fn(x)``: with ``H < E`` that is this share's PART of the
    layer (what the absent experts would add is another device's to
    compute; nothing here stands in for it or for the exchange), with
    ``H == E`` the whole layer.

    No capacity: the picks that land here are sorted by expert into a row
    buffer and ``jax.lax.ragged_dot`` multiplies each expert's rows by its
    matrices.  The buffer's static bound is ``T * top_k`` rows (every
    pick of every token may be a held expert's, and none is dropped); the
    products' time follows the rows that did land (``MoEAux.held_rows``),
    since ``ragged_dot`` visits only the tiles its ``group_sizes`` cover;
    the two moves around them cost what the buffer costs, whatever landed
    (``_rows_of_tokens``, ``_tokens_of_rows``).

    Everything the route hands on (the picks, their scores, ``held``,
    ``segment``, ``order``, ``inverse``, ``group_sizes``, the rows'
    weights) carries :data:`ROUTE_SAVED`: each is a residual of this
    layer's backward, and together they are a few [T * k] vectors, so a
    checkpoint around the layer that saves the name runs the route once a
    step and not again in its recompute.
    """
    t, d = x.shape
    e_held = w_up.shape[0]
    k = int(top_k)
    m = t * k
    with jax.named_scope("hvdt.moe.route"):
        scores, experts, weights = moe_route(
            x, w_router, top_k=k, score=score, normalize=normalize,
            scale=scale, select_bias=select_bias,
            normalize_eps=normalize_eps)
        local = experts.reshape(m) - experts_first            # pick t*k + i
        held = _saved(jnp.logical_and(local >= 0, local < e_held))
        # Held picks first, by expert; the others behind them.
        segment = _saved(jnp.where(held, local, e_held))
        order = _saved(jnp.argsort(segment, stable=True))
        inverse = _saved(jnp.argsort(order))
        group_sizes = _saved(jnp.sum(
            local[:, None] == jnp.arange(e_held, dtype=local.dtype)[None],
            axis=0, dtype=jnp.int32))
        # A row's weight: its pick's, 0 behind the rows that landed.
        weight_of_row = _saved(
            jnp.where(held, weights.reshape(m), 0.0)[order])
        held = held.reshape(t, k)
    with (jax.named_scope("hvdt.moe.dispatch"),
          jax.named_scope("hvdt.moe.dispatch.rows")):
        xs = _rows_of_tokens(x, order, inverse, held, segment, e_held + 1)
    with jax.named_scope("hvdt.moe.experts"):
        up = lax.ragged_dot(xs, w_up.astype(x.dtype), group_sizes)
        if w_gate is None:
            mid = jax.nn.silu(up)
        else:
            mid = jax.nn.silu(lax.ragged_dot(
                xs, w_gate.astype(x.dtype), group_sizes)) * up
        # w (mid W_down) = (w mid) W_down: the weight rides the narrow
        # side of the last product, inside the activation's fusion.
        mid = mid * weight_of_row[:, None].astype(mid.dtype)
        ys = lax.ragged_dot(mid, w_down.astype(x.dtype), group_sizes)
    with (jax.named_scope("hvdt.moe.dispatch"),
          jax.named_scope("hvdt.moe.dispatch.tokens")):
        out = _tokens_of_rows(ys, order, inverse, held, segment, e_held + 1)
    if shared_fn is not None:
        with jax.named_scope("hvdt.moe.shared"):
            out = out + shared_fn(x)

    e_routed = scores.shape[-1]
    frac = jnp.sum(jax.nn.one_hot(experts, e_routed, dtype=jnp.float32),
                   axis=(0, 1)) / m
    mean_score = (scores / jnp.maximum(scores.sum(-1, keepdims=True), 1e-20)
                  ).mean(0)
    aux = MoEAux(
        load_balance_loss=e_routed * jnp.sum(frac * mean_score),
        dropped_fraction=jnp.zeros((), jnp.float32),   # by construction
        held_rows=group_sizes.sum().astype(jnp.float32),
        max_expert_rows=group_sizes.max().astype(jnp.float32))
    return out, aux


def _a2a_transport(block: jax.Array, axis: str, name: str):
    """``lax.all_to_all`` over ``axis`` with the transport policy's wire.

    ``block`` is ``[ep, ...]`` (leading dim = axis size; slice i goes to
    rank i).  Resolves ``axis`` against ``HVDT_TRANSPORT`` exactly like
    the fused allreduce: an int8 wire sends block-scaled int8 payloads +
    f32 scales (two alltoalls, f32 restore on arrival); bf16/fp16 cast
    down for the flight; unset keeps the exact-dtype exchange.  Books
    the trace-time collective counters and one flight-recorder event
    per traced program."""
    from ..telemetry import flight_recorder as _frm
    from ..telemetry import instrument as _ti
    from ..transport import policy as _tpolicy

    _res = _tpolicy.resolve_axis(axis)
    wire = _res.fast.wire if _res is not None else None

    orig_dtype = block.dtype
    ep = block.shape[0]
    rest = int(block.size) // ep
    payload_bytes = int(block.size) * jnp.dtype(orig_dtype).itemsize
    wire_label = jnp.dtype(orig_dtype).name

    def _a2a(x):
        return lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                              tiled=False)

    int8_wire = (wire == "int8"
                 and jnp.issubdtype(orig_dtype, jnp.floating))
    cast_wire = (wire in ("bf16", "fp16")
                 and jnp.issubdtype(orig_dtype, jnp.floating))

    if int8_wire:
        from ..quant.kernels import (dequantize_flat, quant_block_size,
                                     quantize_flat)

        shape = block.shape
        bs = quant_block_size()
        pad = (-rest) % bs
        rows = block.reshape(ep, rest).astype(jnp.float32)
        if pad:
            rows = jnp.concatenate(
                [rows, jnp.zeros((ep, pad), jnp.float32)], axis=1)
        padded = rest + pad
        # Row boundaries align with block boundaries after padding, so
        # one flat quantize covers all rows.
        q, scales = quantize_flat(rows.reshape(-1), bs)
        wire_label = "int8_blockwise"
        payload_bytes = int(q.size) + int(scales.size) * 4
        with jax.named_scope(f"hvdt.moe_a2a.{name}"):
            recv_q = _a2a(q.reshape(ep, padded))
            recv_s = _a2a(scales.reshape(ep, padded // bs))
        out = dequantize_flat(recv_q.reshape(-1),
                              recv_s.reshape(-1), bs)
        out = out.reshape(ep, padded)
        if pad:
            out = out[:, :rest]
        result = out.reshape(shape).astype(orig_dtype)
    else:
        x = block
        if cast_wire:
            wdt = jnp.bfloat16 if wire == "bf16" else jnp.float16
            x = x.astype(wdt)
            wire_label = jnp.dtype(wdt).name
            payload_bytes = int(x.size) * jnp.dtype(wdt).itemsize
        with jax.named_scope(f"hvdt.moe_a2a.{name}"):
            result = _a2a(x)
        if result.dtype != orig_dtype:
            result = result.astype(orig_dtype)

    _rec = _ti.get_recorder()
    _flight = _frm.get_flight_recorder()
    if _rec is not None:
        _rec.record_collective(
            "alltoall", jnp.dtype(orig_dtype).name, wire_label,
            payload_bytes, count=1, path="jit", axis=axis)
    if _flight is not None:
        _flight.record(
            op="alltoall", name=name, dtype=jnp.dtype(orig_dtype).name,
            shape=tuple(int(s) for s in block.shape),
            nbytes=payload_bytes, wire=wire_label, path="jit",
            count=1, axis=axis)
    return result


def moe_dispatch_combine(tokens: jax.Array,
                         router_logits: jax.Array,
                         expert_fn: Callable[[jax.Array], jax.Array],
                         *,
                         axis: str = "ep",
                         experts_per_rank: int = 1,
                         capacity_factor: Optional[float] = None,
                         top_k: Optional[int] = None
                         ) -> Tuple[jax.Array, MoEAux]:
    """Route each token to its top-k experts across the ``ep`` axis.

    Must run inside shard_map with ``axis`` bound.  Tokens over a full
    expert's capacity are dropped (residual passthrough — standard switch
    behavior); primary (k=0) choices claim capacity before secondary
    ones, so overflow sheds the lowest-gate assignments first.

    Args:
      tokens: local tokens ``[T, D]``.
      router_logits: ``[T, E]`` where ``E = ep_size * experts_per_rank``.
      expert_fn: vmapped-over-experts body ``[E_local, N, D] -> [E_local, N, D]``.
      capacity_factor: per-expert slots = ceil(T·k/E · factor); defaults
        to ``HVDT_MOE_CAPACITY_FACTOR`` (1.25).
      top_k: experts per token, gates renormalized over the chosen k;
        defaults to ``HVDT_MOE_TOPK`` (1, switch routing).

    Returns (combined ``[T, D]``, MoEAux).
    """
    if capacity_factor is None:
        capacity_factor = _env_float("HVDT_MOE_CAPACITY_FACTOR", 1.25)
    if top_k is None:
        top_k = _env_int("HVDT_MOE_TOPK", 1)
    k = max(1, int(top_k))
    t, d = tokens.shape
    ep = _axis_size_static(axis)
    e_total = ep * experts_per_rank
    if router_logits.shape[-1] != e_total:
        raise ValueError(
            f"router logits last dim {router_logits.shape[-1]} != "
            f"ep*experts_per_rank = {e_total}")
    if k > e_total:
        raise ValueError(f"top_k={k} exceeds {e_total} experts")
    cap = moe_capacity(t, e_total, top_k=k,
                       capacity_factor=capacity_factor)

    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    top_vals, top_idx = lax.top_k(probs, k)                  # [T, K]
    gates = top_vals / jnp.maximum(
        top_vals.sum(-1, keepdims=True), 1e-9)               # [T, K]

    # Flatten choices k-major ([K*T]): row k*T + t is token t's k-th
    # choice, so the cumsum hands capacity to every primary assignment
    # before any secondary one.
    expert_f = top_idx.T.reshape(-1)                         # [K*T]
    gate_f = gates.T.reshape(-1)                             # [K*T]
    tokens_f = jnp.tile(tokens, (k, 1))                      # [K*T, D]

    one_hot = jax.nn.one_hot(expert_f, e_total, dtype=jnp.float32)
    pos = (jnp.cumsum(one_hot, axis=0) - one_hot) * one_hot  # [K*T, E]
    pos_in_expert = pos.sum(-1).astype(jnp.int32)            # [K*T]
    kept = pos_in_expert < cap

    # Scatter local tokens into [E, cap, D] dispatch slots.
    dispatch = jnp.zeros((e_total, cap, d), tokens.dtype)
    idx_e = jnp.where(kept, expert_f, 0)
    idx_c = jnp.where(kept, pos_in_expert, 0)
    weight = jnp.where(kept, 1.0, 0.0)
    dispatch = dispatch.at[idx_e, idx_c].add(
        tokens_f * weight[:, None].astype(tokens.dtype))

    # [E, cap, D] -> [ep, E_local, cap, D] -> alltoall over ep.
    dispatch = dispatch.reshape(ep, experts_per_rank, cap, d)
    recv = _a2a_transport(dispatch, axis, "moe.dispatch")
    # Fold source-rank dim into the capacity dim for the expert body.
    recv = recv.transpose(1, 0, 2, 3).reshape(experts_per_rank, ep * cap, d)
    processed = expert_fn(recv)
    processed = processed.reshape(experts_per_rank, ep, cap, d).transpose(
        1, 0, 2, 3)
    back = _a2a_transport(processed, axis, "moe.combine")
    back = back.reshape(e_total, cap, d)

    # Combine: gather each kept slot, weight by its renormalized gate.
    slots = back[idx_e, idx_c] * (gate_f * weight).astype(
        tokens.dtype)[:, None]                               # [K*T, D]
    out = slots.reshape(k, t, d).sum(axis=0)

    # Switch-transformer load-balancing loss over the PRIMARY routing:
    # E * Σ_e f_e · P_e, where f is the top-1 routed fraction and P the
    # mean router prob — averaged globally (reduces to the classic
    # switch loss at k=1).
    primary = jax.nn.one_hot(top_idx[:, 0], e_total, dtype=jnp.float32)
    f = lax.pmean(primary.mean(axis=0), axis)
    p_mean = lax.pmean(probs.mean(axis=0), axis)
    aux = MoEAux(
        load_balance_loss=e_total * jnp.sum(f * p_mean),
        dropped_fraction=lax.pmean(1.0 - kept.mean(), axis))

    from ..telemetry import instrument as _ti

    _rec = _ti.get_recorder()
    if _rec is not None:
        # Static routing geometry, booked at trace time (path=jit
        # convention): slot count and the slot/token expansion the
        # capacity factor buys.
        _rec.registry.gauge(
            "hvdt_moe_capacity_slots",
            "Per-expert dispatch slots of the last traced MoE layer "
            "(ceil(T*k/E * capacity_factor))").set(float(cap))
        _rec.registry.gauge(
            "hvdt_moe_expansion_ratio",
            "Dispatch slots / routed assignments of the last traced "
            "MoE layer (capacity head-room; <1 guarantees drops)"
        ).set(float(cap * e_total) / float(t * k))
    return out, aux


def report_moe_aux(aux: MoEAux, *, step: Optional[int] = None) -> None:
    """Host-side per-step reporter for the routing aux outputs.

    The traced program returns ``MoEAux`` as arrays; the train loop
    calls this after the step to surface them as ``hvdt_moe_*`` gauges
    (attribution-plane idiom — the time-series/anomaly layer picks the
    gauges up from the registry).  No-op when telemetry is off."""
    from ..telemetry import instrument as _ti

    _rec = _ti.get_recorder()
    if _rec is None:
        return
    del step
    _rec.registry.gauge(
        "hvdt_moe_load_balance_loss",
        "Switch-transformer load-balance aux loss of the last "
        "reported step (E * sum_e f_e * P_e)").set(
        float(jax.device_get(aux.load_balance_loss)))
    _rec.registry.gauge(
        "hvdt_moe_dropped_fraction",
        "Fraction of routed token assignments dropped over expert "
        "capacity in the last reported step").set(
        float(jax.device_get(aux.dropped_fraction)))
    if aux.held_rows is not None:
        _rec.registry.gauge(
            "hvdt_moe_held_rows",
            "Token-expert picks that landed on the experts held here in "
            "the last reported step (rows of the dropless layer's grouped "
            "products)").set(float(jax.device_get(aux.held_rows)))
        _rec.registry.gauge(
            "hvdt_moe_max_expert_rows",
            "Rows of the fullest held expert in the last reported "
            "step").set(float(jax.device_get(aux.max_expert_rows)))
