"""Ring attention: exact attention over sequence shards via an ICI ring.

Long-context substrate (SURVEY.md §5.7 — absent upstream; the reference's
only sequence-adjacent primitive is alltoall, operations.cc:1642).  Design
follows the ring-attention pattern: Q stays put, K/V blocks rotate around
the ``sp`` mesh axis with ``lax.ppermute`` while each device accumulates
its block's contribution with flash-style (log-sum-exp) running statistics,
so per-step memory is O(block) and comm overlaps compute under XLA async
dispatch.

Must be called inside ``shard_map``/pjit where the ``sp`` axis is bound and
the sequence dimension of q/k/v is the *local* shard.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.device import _axis_size_static

__all__ = ["ring_attention"]

_NEG_INF = -1e30


def _block_update(q, k, v, acc, row_max, row_sum, mask, scale):
    """One flash-attention block accumulation step.

    q: [B, Lq, H, D]; k/v: [B, Lk, Hkv, D] (Hkv divides H — expanded here,
    after the ring transfer, so the ppermute only ever moves the small
    unexpanded K/V); acc: [B, Lq, H, D]; row_max/row_sum: [B, H, Lq];
    mask: broadcastable to [B, H, Lq, Lk].
    """
    h, kv_heads = q.shape[2], k.shape[2]
    if h != kv_heads:
        k = jnp.repeat(k, h // kv_heads, axis=2)
        v = jnp.repeat(v, h // kv_heads, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask, scores, _NEG_INF)
    new_max = jnp.maximum(row_max, scores.max(axis=-1))
    # exp() of masked rows would be exp(0)=1 when the whole row is masked
    # (scores == new_max == -inf); re-mask explicitly.
    p = jnp.where(mask, jnp.exp(scores - new_max[..., None]), 0.0)
    correction = jnp.exp(row_max - new_max)
    acc = acc * correction.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32)
    row_sum = row_sum * correction + p.sum(axis=-1)
    return acc, new_max, row_sum


def _bwd_block_grads(qf, dof, k_blk, v_blk, lse, delta_bhq, mask, scale,
                     group):
    """One visiting K/V block's (dq, dk, dv) contributions in the jnp
    ring backward — scores recomputed from the saved logsumexp.

    qf/dof: f32 ``[B, Lq, H, D]``; k_blk/v_blk: raw ``[B, Lk, Hkv, D]``;
    lse: ``[B, H, Lq]``; delta_bhq: ``[B, H, Lq]``; mask: broadcastable
    to ``[B, H, Lq, Lk]`` or None (fully visible); group = H // Hkv.
    Called from :func:`_ring_diff_bwd`'s scan body.
    """
    f32 = jnp.float32
    ks = k_blk.astype(f32)
    vs = v_blk.astype(f32)
    if group > 1:
        ks = jnp.repeat(ks, group, axis=2)
        vs = jnp.repeat(vs, group, axis=2)
    s_ = jnp.einsum("bqhd,bkhd->bhqk", qf, ks) * scale
    p = jnp.exp(s_ - lse[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    dv_c = jnp.einsum("bhqk,bqhd->bkhd", p, dof)
    dp = jnp.einsum("bqhd,bkhd->bhqk", dof, vs)
    ds = p * (dp - delta_bhq[..., None]) * scale
    dq_c = jnp.einsum("bhqk,bkhd->bqhd", ds, ks)
    dk_c = jnp.einsum("bhqk,bqhd->bkhd", ds, qf)
    if group > 1:
        b, lk = k_blk.shape[0], k_blk.shape[1]
        hkv, d = k_blk.shape[2], k_blk.shape[3]
        dk_c = dk_c.reshape(b, lk, hkv, group, d).sum(3)
        dv_c = dv_c.reshape(b, lk, hkv, group, d).sum(3)
    return dq_c, dk_c, dv_c


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   *,
                   axis: str = "sp",
                   causal: bool = True,
                   scale: Optional[float] = None,
                   segment_ids: Optional[jax.Array] = None,
                   use_pallas: Optional[bool] = None) -> jax.Array:
    """Exact (optionally causal) attention over a sequence-sharded ring.

    Differentiation: the common path (``segment_ids=None``) carries a
    ``custom_vjp`` whose backward is a SECOND ring pass that recomputes
    scores blockwise from the saved logsumexp — O(local_seq x block)
    memory, like the forward.  Plain autodiff through the forward scan
    would instead save every visiting block's score matrix
    (O(local_seq x global_seq) per device), which defeats the point of
    sequence parallelism at long context.  The ``segment_ids`` path
    still differentiates that way (exact, memory-heavy).  With
    ``use_pallas=True`` BOTH ring passes run Pallas kernels
    (ops/pallas_kernels.flash_block_update forward,
    flash_grad_block backward) — fully trainable.

    Args:
      q, k, v: local shards ``[batch, local_seq, heads, head_dim]``.  MQA/GQA
        is supported: k/v may have fewer heads as long as q heads divide;
        the ring only ever transfers the unexpanded K/V.
      axis: mesh axis name carrying the sequence shards.
      causal: apply a causal mask using *global* positions.
      scale: score scale; default ``1/sqrt(head_dim)``.
      segment_ids: optional ``[batch, local_seq]`` int segment labels for
        packed sequences; attention is masked to equal segments.  The key
        side's labels rotate around the ring with K/V.
      use_pallas: run each ring step through the Pallas flash kernels —
        ops/pallas_kernels.flash_block_update forward,
        flash_grad_block backward (dK/dV accumulated blockwise in VMEM
        scratch and rotated with their block) — instead of the jnp
        block update.  Trainable: grads match the jnp path and the
        dense reference (tests/test_parallel.py).  Default **False**
        (requires segment_ids=None and 128-tiling shapes; the jnp path
        is the portable default).

    Returns ``[batch, local_seq, heads, head_dim]`` in q's dtype.
    """
    b, lq, h, d = q.shape
    if h % k.shape[2]:
        raise ValueError(
            f"q heads {h} not divisible by kv heads {k.shape[2]}")
    if scale is None:
        scale = d ** -0.5
    lk = k.shape[1]

    kernel_legal = (segment_ids is None
                    and not (lq % min(128, lq) or lk % min(128, lk)))
    if use_pallas is None:
        # Env-driven default (HVDT_RING_PALLAS=1): engage the kernels
        # where they are legal, silently keep the jnp path elsewhere.
        from ..common import config

        use_pallas = config.get_bool("HVDT_RING_PALLAS") and kernel_legal
    elif use_pallas and not kernel_legal:
        import warnings

        warnings.warn(
            "ring_attention(use_pallas=True) ignored: the kernel needs "
            "segment_ids=None and 128-tiling shapes "
            f"(lq={lq}, lk={lk}); running the jnp block update",
            stacklevel=2)
        use_pallas = False

    # The custom_vjp path needs scale as a static Python float
    # (nondiff arg); a traced scale (e.g. a learned temperature) keeps
    # the plain-autodiff path, which handles it fine.
    try:
        static_scale = float(scale)
    except Exception:
        static_scale = None
    if segment_ids is None and static_scale is not None:
        return _ring_diff(q, k, v, axis, causal, static_scale, use_pallas)
    out, _ = _ring_forward(q, k, v, axis, causal, scale,
                           segment_ids, use_pallas)
    return out


def _ring_forward(q, k, v, axis, causal, scale, segment_ids, use_pallas):
    """Forward ring pass; returns (out, lse [B,H,Lq])."""
    b, lq, h, d = q.shape
    sp = _axis_size_static(axis)
    my = lax.axis_index(axis)
    lk = k.shape[1]

    q_pos = my * lq + jnp.arange(lq)                      # global q positions

    # Initial accumulators must carry the same varying-manual-axes type the
    # scan body produces (q/k/v's vma plus the ring axis) so the carry is
    # type-stable — q may additionally vary over dp/tp axes of the mesh.
    from .sharding import pcast_to_union

    def _varying(x):
        return pcast_to_union(x, q, k, v, extra=(axis,))

    acc = _varying(jnp.zeros((b, lq, h, d), jnp.float32))
    row_max = _varying(jnp.full((b, h, lq), _NEG_INF, jnp.float32))
    row_sum = _varying(jnp.zeros((b, h, lq), jnp.float32))
    fwd = [(i, (i + 1) % sp) for i in range(sp)]
    k_seg0 = segment_ids if segment_ids is not None else None

    def step(carry, s):
        k_blk, v_blk, k_seg, acc, row_max, row_sum = carry
        # After s rotations the resident block originated at rank (my - s).
        src = (my - s) % sp
        if use_pallas:
            # Fused VMEM-resident block update (ops/pallas_kernels.py).
            # Ring blocks need only three mask cases — source block fully
            # visible (src < my), the causal diagonal (src == my), or
            # fully in the future (identity) — so the kernel's position
            # offsets stay static and lax.switch picks the case.
            from ..ops.pallas_kernels import flash_block_update

            def _full(ops):
                qq, kb, vb, a, m_, s_ = ops
                return flash_block_update(qq, kb, vb, a, m_, s_,
                                          q_offset=0, k_offset=0,
                                          causal=False, scale=scale)

            def _diag(ops):
                qq, kb, vb, a, m_, s_ = ops
                return flash_block_update(qq, kb, vb, a, m_, s_,
                                          q_offset=0, k_offset=0,
                                          causal=True, scale=scale)

            def _skip(ops):
                _, _, _, a, m_, s_ = ops
                return a, m_, s_

            ops_in = (q, k_blk, v_blk, acc, row_max, row_sum)
            if causal:
                case = jnp.where(src < my, 0, jnp.where(src == my, 1, 2))
                acc, row_max, row_sum = lax.switch(
                    case, [_full, _diag, _skip], ops_in)
            else:
                acc, row_max, row_sum = _full(ops_in)
        else:
            k_pos = src * lk + jnp.arange(lk)
            if causal:
                mask = (q_pos[:, None] >= k_pos[None, :])[None, None]
            else:
                mask = jnp.ones((1, 1, 1, 1), bool)
            if k_seg is not None:
                same = segment_ids[:, :, None] == k_seg[:, None, :]
                mask = jnp.logical_and(mask, same[:, None, :, :])
            acc, row_max, row_sum = _block_update(
                q, k_blk, v_blk, acc, row_max, row_sum, mask, scale)
        # Rotate K/V (and its segment labels) forward for the next step.
        k_nxt = lax.ppermute(k_blk, axis, fwd)
        v_nxt = lax.ppermute(v_blk, axis, fwd)
        seg_nxt = (lax.ppermute(k_seg, axis, fwd)
                   if k_seg is not None else None)
        return (k_nxt, v_nxt, seg_nxt, acc, row_max, row_sum), None

    (_, _, _, acc, row_max, row_sum), _ = lax.scan(
        step, (k, v, k_seg0, acc, row_max, row_sum), jnp.arange(sp))
    row_sum = jnp.maximum(row_sum, 1e-30)
    out = acc / row_sum.transpose(0, 2, 1)[..., None]
    lse = row_max + jnp.log(row_sum)                       # [B, H, Lq]
    return out.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_diff(q, k, v, axis, causal, scale, use_pallas):
    out, _ = _ring_forward(q, k, v, axis, causal, scale, None, use_pallas)
    return out


def _ring_diff_fwd(q, k, v, axis, causal, scale, use_pallas):
    out, lse = _ring_forward(q, k, v, axis, causal, scale, None, use_pallas)
    return out, (q, k, v, out, lse)


def _ring_diff_bwd(axis, causal, scale, use_pallas, res, do):
    """Second ring pass: dk/dv accumulators travel WITH their K/V block
    (ppermute) and arrive home after sp rotations carrying every rank's
    contribution; dq accumulates locally.  Scores are recomputed per
    visiting block from the saved logsumexp — O(local_seq x block)
    memory, mirroring the forward."""
    q, k, v, out, lse = res
    b, lq, h, d = q.shape
    lk, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    sp = _axis_size_static(axis)
    my = lax.axis_index(axis)
    fwd = [(i, (i + 1) % sp) for i in range(sp)]
    f32 = jnp.float32

    qf = q.astype(f32)
    dof = do.astype(f32)
    # delta_i = sum_d do_i * o_i (rowsum term of dS)       [B, Lq, H]
    delta = jnp.einsum("bqhd,bqhd->bqh", do, out,
                       preferred_element_type=f32)
    q_pos = my * lq + jnp.arange(lq)

    from .sharding import pcast_to_union

    def _varying(x):
        return pcast_to_union(x, q, k, v, do, extra=(axis,))

    delta, lse_v = _varying(delta), _varying(lse)
    qf, dof = _varying(qf), _varying(dof)

    if use_pallas:
        # Per-step grads through the Pallas backward kernels
        # (ops/pallas_kernels.flash_grad_block): the VMEM-tiled
        # recompute of this block pair's (dq, dk, dv) — no [B,H,Lq,Lk]
        # f32 score tensor in HBM.  Ring blocks need only the three
        # static mask cases of the forward (full/diagonal/future), so
        # the kernels see static causal flags and zero offsets.
        from ..ops.pallas_kernels import flash_grad_block

        qv, dov, outv = _varying(q), _varying(do), _varying(out)
        delta_bhq = _varying(delta.transpose(0, 2, 1))        # [B,H,Lq]

        def _grads(kb, vb, causal_flag):
            return flash_grad_block(qv, kb, vb, dov, outv, lse_v,
                                    causal=causal_flag, scale=scale,
                                    delta=delta_bhq)

        def pstep(carry, s):
            k_blk, v_blk, dk_blk, dv_blk, dq_acc = carry
            src = (my - s) % sp

            def _full(ops):
                return _grads(ops[0], ops[1], False)

            def _diag(ops):
                return _grads(ops[0], ops[1], True)

            def _skip(ops):
                return (_varying(jnp.zeros((b, lq, h, d), f32)),
                        _varying(jnp.zeros((b, lk, hkv, d), f32)),
                        _varying(jnp.zeros((b, lk, hkv, d), f32)))

            if causal:
                case = jnp.where(src < my, 0, jnp.where(src == my, 1, 2))
                dq_c, dk_c, dv_c = lax.switch(
                    case, [_full, _diag, _skip], (k_blk, v_blk))
            else:
                dq_c, dk_c, dv_c = _full((k_blk, v_blk))
            return (lax.ppermute(k_blk, axis, fwd),
                    lax.ppermute(v_blk, axis, fwd),
                    lax.ppermute(dk_blk + dk_c, axis, fwd),
                    lax.ppermute(dv_blk + dv_c, axis, fwd),
                    dq_acc + dq_c), None

        zeros_kv = _varying(jnp.zeros((b, lk, hkv, d), f32))
        dq0 = _varying(jnp.zeros((b, lq, h, d), f32))
        (_, _, dk, dv, dq), _ = lax.scan(
            pstep, (k, v, zeros_kv, zeros_kv, dq0), jnp.arange(sp))
        return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))

    delta_bhq = delta.transpose(0, 2, 1)                      # [B,H,Lq]

    def step(carry, s):
        k_blk, v_blk, dk_blk, dv_blk, dq_acc = carry
        src = (my - s) % sp
        if causal:
            k_pos = src * lk + jnp.arange(lk)
            mask = (q_pos[:, None] >= k_pos[None, :])[None, None]
        else:
            mask = None
        dq_c, dk_c, dv_c = _bwd_block_grads(
            qf, dof, k_blk, v_blk, lse_v, delta_bhq, mask, scale, group)
        dq_acc = dq_acc + dq_c
        dk_blk = dk_blk + dk_c
        dv_blk = dv_blk + dv_c
        return (lax.ppermute(k_blk, axis, fwd),
                lax.ppermute(v_blk, axis, fwd),
                lax.ppermute(dk_blk, axis, fwd),
                lax.ppermute(dv_blk, axis, fwd),
                dq_acc), None

    zeros_kv = _varying(jnp.zeros((b, lk, hkv, d), f32))
    dq0 = _varying(jnp.zeros((b, lq, h, d), f32))
    (_, _, dk, dv, dq), _ = lax.scan(
        step, (k, v, zeros_kv, zeros_kv, dq0), jnp.arange(sp))
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


_ring_diff.defvjp(_ring_diff_fwd, _ring_diff_bwd)
