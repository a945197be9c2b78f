"""Pallas TPU kernels for the attention hot path.

The framework's compute plane is XLA; Pallas is reserved for the ops
where profiling shows XLA's fusion isn't enough (SURVEY.md §7: "Pallas
only if profiling demands").  Attention is that op: the naive einsum
materializes the [B,H,Lq,Lk] score matrix in HBM, while the flash kernel
streams K/V blocks through VMEM with an online softmax — HBM traffic
drops from O(L²) to O(L·D), which is the difference between
bandwidth-bound and MXU-bound at long sequence.

Two entry points, one tile body (``_online_softmax_update``):

* ``flash_attention(q, k, v)`` — fused causal/full attention for the
  non-ring path (one device holds the whole sequence).  Its forward is
  one self-contained call (``_flash_local_call``): q, k, v in as
  [B, L, H*D] rows, ``out`` in the same layout and the activation dtype
  and the f32 logsumexp out; the running
  (acc, row_max, row_sum) lives in VMEM scratch from the first K/V block
  to the last and never touches HBM.  Its backward is one call too
  (``_flash_local_bwd_call``): q, k, v, dO and the two f32 row statistics
  in, dq, dk, dv in the activation dtype out, the three accumulators in
  VMEM scratch.
* ``flash_block_update(...)`` — one ring-attention step: takes the
  running (acc, row_max, row_sum) online-softmax carry and a K/V block
  (with its global position offset), returns the updated carry
  (``_flash_call``: a ring step has to hand its state to the next one,
  so this call keeps the carry in HBM on both sides).
  ``parallel/ring_attention.py`` composes it around ``lax.ppermute``, and
  ``flash_grad_block`` for its backward: global offsets in, f32 partial
  sums out, for the same reason.

Other kernels are not flash attention.  ``rope(x, cos, sin, half)`` is
the rotary embedding on the same [B, L, H*D] rows, between the projections
and the flash calls: elementwise, and a kernel only because XLA, asked to
slice a head in halves narrower than a lane tile, lays the whole attention
block sequence-minor and copies it to and from the flash calls.
``unit_lower_inverse_slabs(cols)`` is the inverse of I + A for the Gated
DeltaNet scan's chunks (``ops/gated_delta.py``), whose 64 sequential row
steps XLA can only run as 64 passes over HBM and a program here runs on a
block in VMEM.  ``gdn_chunk_forward`` / ``gdn_chunk_backward`` are the
Mosaic schedule of that scan's other chunk-local passes and of their
hand-written differentiation rule (``ops/gated_delta._chunk_passes``): a
``before`` call (the norms, ``K K^T``, ``Q K^T``, the decay ratios, ``A``,
``attn``), the solve's call between two transposes that XLA makes, an
``after`` call (``T (beta V)``, ``T (beta gamma K)``, ``(gamma_C / gamma)
K``) and one call for the whole backward; each takes q, k, v as column
blocks of the projection's own [B, L, 2 Kd + Vd] rows and holds a block
of chunks of one key head in VMEM.  ``ssd_chunk_state`` / ``ssd_chunk_out``
and their two backwards are the Mosaic schedule of the Mamba-2 scan's
chunk passes on both sides of its state's loop and of their hand-written
rules (``ops/ssd._chunk_state`` / ``_chunk_out``): a program holds a chunk
and 8 heads, reads x, B and C as column blocks of the convolution's own
[B, L, I + 2 S] rows, keeps a head's [C, C] masked pairs in VMEM and
writes y once, token-major.

Which of the two runs is decided by which function the caller calls,
and by nothing a user sets.  Both run in Pallas interpret mode off-TPU,
so the CPU test suite exercises the very same kernel code
(tests/test_pallas.py compares against the jnp reference).

Layout: the local kernels work on the projections' own [B, L, H*D] rows,
a bitcast from the framework's [B, L, H, D]: a head, or the group of heads
that fills 128 lanes, is a block index on the last dimension
(``_heads_per_program``), and nothing is transposed around the calls; only
a shape with no such block (H odd at head_dim 64, grouped queries under
head_dim 128) folds its heads into the batch first (``_rows_layout``).
The ring kernels work in [B, H, L, D] and their wrappers transpose.  The
local forward's logsumexp leaves as lane-dense rows [B, H, 1, L], not as a
[B, H, L, 1] column, which HBM pads to 128 lanes.  GQA/MQA is handled in
the BlockSpec index maps (kv head = q head // group) — K/V are never
materially expanded.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..telemetry.compile_ledger import kernel_scope

__all__ = ["flash_attention", "flash_attention_stats", "flash_block_update",
           "flash_grad_block", "rope", "moe_sum_rows", "eva_summary_tiles",
           "eva_summary_attention", "attention_reference"]

_NEG_INF = -1e30


def _use_interpret() -> bool:
    return jax.devices()[0].platform != "tpu"


def _vma_kw(*ops) -> dict:
    """``{"vma": ...}`` kwargs for pallas_call out_shapes: inside
    shard_map (check_vma) out types must carry the varying-axes set, and
    outputs vary over every axis any operand varies over.  The empty set
    too: all-invariant operands (psum'd gradients) need a non-None vma."""
    vma = frozenset()
    for op in ops:
        vma |= frozenset(jax.typeof(op).vma)
    return {"vma": vma}


def _sublane_tile(*dtypes) -> int:
    """Rows of the largest sublane tile among ``dtypes``: 8 for 4-byte
    types, 16 for 2-byte, 32 for 1-byte."""
    return max({4: 8, 2: 16, 1: 32}.get(jnp.dtype(d).itemsize, 8)
               for d in dtypes)


def _fit_block(n: int, block: int, *dtypes) -> int:
    """Largest power-of-2 reduction of ``block`` that divides ``n`` (the
    defaults are tuned upper bounds, not divisibility requirements —
    callers gate on 128-divisible sequence lengths, so this lands on
    >=128 for them and degrades gracefully for anything else).

    On real TPU the block's sublane dimension must stay tile-aligned
    (the per-dtype minimum sublane tile: 8 rows for f32, 16 for bf16,
    32 for 1-byte types); Mosaic fails to lower smaller blocks with an
    obscure error, so refuse explicitly instead.  Interpret mode (the
    CPU test path) has no alignment floor."""
    fitted = min(block, n)
    while n % fitted:
        fitted //= 2
    fitted = max(fitted, 1)
    floor = _sublane_tile(*dtypes)
    if fitted < floor and not _use_interpret():
        names = "/".join(jnp.dtype(d).name for d in dtypes)
        raise ValueError(
            f"sequence length {n} only tiles at block={fitted}, below the "
            f"TPU sublane tile ({floor} rows for {names}) "
            f"— pad the sequence to a multiple of 128")
    return fitted


def _online_softmax_update(s, v_blk, acc_s, m_s, l_s, mask=None,
                           rows_may_be_empty=False):
    """The tile arithmetic both flash forwards share: fold one K/V block,
    whose f32 score tile is ``s`` [bq, bk], into the running (acc, m, l)
    in VMEM scratch.  ``mask`` (True = visible) is None on a tile with
    nothing to hide.  A masked score is -1e30, so its exp underflows to an
    exact 0 once the row's max is finite; only a caller whose rows can
    still be at the initial max (``rows_may_be_empty``) pays a second
    select to zero them."""
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m = m_s[...]
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    if mask is not None and rows_may_be_empty:
        p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m - m_new)
    acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
        p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    l_s[...] = l_s[...] * corr + p.sum(axis=-1, keepdims=True)
    m_s[...] = m_new


def _kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
            oacc_ref, om_ref, ol_ref, acc_s, m_s, l_s, *, causal: bool,
            scale: float):
    """Grid program (b, h, iq, ik): one K/V block per step, online softmax.

    The canonical TPU flash layout: ik is the innermost (sequential) grid
    dim, so K/V stream through VMEM with pipelined double-buffering while
    the (acc, m, l) state lives in persistent VMEM scratch — initialized
    from the carry inputs at ik==0, flushed to the outputs at the last ik.
    qo/ko: scalar-prefetch global position offsets (SMEM) for the causal
    mask; q_ref: [1,1,bq,d]; k_ref/v_ref: [1,1,bk,d].
    """
    import jax.experimental.pallas as pl

    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]

    @pl.when(ik == 0)
    def _init():
        acc_s[...] = acc_ref[0, 0, :, :].astype(jnp.float32)
        m_s[...] = m_ref[0, 0, :, :].astype(jnp.float32)
        l_s[...] = l_ref[0, 0, :, :].astype(jnp.float32)

    def _compute():
        s = jax.lax.dot_general(
            q_ref[0, 0, :, :], k_ref[0, 0, :, :],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        mask = None
        if causal:
            q_pos = (qo_ref[0] + iq * bq
                     + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0))
            k_pos = (ko_ref[0] + ik * bk
                     + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1))
            mask = q_pos >= k_pos               # [bq, bk]
        # A ring step can meet a row with no visible key before any
        # earlier block has given it a finite max: rows_may_be_empty.
        _online_softmax_update(s, v_ref[0, 0, :, :], acc_s, m_s, l_s,
                               mask, rows_may_be_empty=True)

    if causal:
        # Causal block pruning: when even this q-block's LAST row precedes
        # the k-block's first position the whole tile is masked — skip both
        # matmuls (the flops halving that makes causal flash ~2x full).
        last_q = qo_ref[0] + iq * bq + (bq - 1)
        first_k = ko_ref[0] + ik * bk
        pl.when(last_q >= first_k)(_compute)
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _flush():
        oacc_ref[0, 0, :, :] = acc_s[...]
        om_ref[0, 0, :, :] = m_s[...]
        ol_ref[0, 0, :, :] = l_s[...]


def _flash_call(q, k, v, acc, m, l, q_offset, k_offset, *, causal, scale,
                block_q, block_k):
    """The ring step's pallas_call: carry in, carry out.  All operands in
    [B, H(q or kv), L, D] / [B, H, L, 1] layout; returns (acc, m, l)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, lq, d = q.shape
    _, hkv, lk, _ = k.shape
    group = h // hkv
    if lq % block_q or lk % block_k:
        raise ValueError(
            f"seq lens (q={lq}, k={lk}) must divide block sizes "
            f"({block_q}, {block_k})")
    grid = (b, h, lq // block_q, lk // block_k)

    qspec = pl.BlockSpec((1, 1, block_q, d),
                         lambda bb, hh, qq, kk, *_: (bb, hh, qq, 0))
    kvspec = pl.BlockSpec((1, 1, block_k, d),
                          lambda bb, hh, qq, kk, *_: (bb, hh // group, kk, 0))
    carry_d = pl.BlockSpec((1, 1, block_q, d),
                           lambda bb, hh, qq, kk, *_: (bb, hh, qq, 0))
    carry_1 = pl.BlockSpec((1, 1, block_q, 1),
                           lambda bb, hh, qq, kk, *_: (bb, hh, qq, 0))

    kernel = functools.partial(_kernel, causal=causal, scale=scale)
    kw = _vma_kw(q, k, v, acc, m, l)
    out_shapes = (
        jax.ShapeDtypeStruct((b, h, lq, d), jnp.float32, **kw),
        jax.ShapeDtypeStruct((b, h, lq, 1), jnp.float32, **kw),
        jax.ShapeDtypeStruct((b, h, lq, 1), jnp.float32, **kw),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[qspec, kvspec, kvspec, carry_d, carry_1, carry_1],
        out_specs=[carry_d, carry_1, carry_1],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)])
    with kernel_scope("flash_fwd"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=out_shapes,
            interpret=_use_interpret(),
        )(jnp.atleast_1d(q_offset).astype(jnp.int32),
          jnp.atleast_1d(k_offset).astype(jnp.int32),
          q, k, v, acc, m, l)


# ---------------------------------------------------------------------------
# What a query may see.  The local kernels take their mask as static
# arguments: ``causal`` (key j <= query i) and ``window`` (also i - j <
# window, the query's own position counted; None = no window).  Three
# functions say what that means, for the forward and the backward alike,
# and are the one place a further static or per-token condition joins
# (segment ids of packed documents: a term of ``_visible_mask``, and every
# tile "straddles"): which pairs of a tile are visible, whether a tile has
# any or only such pairs, and where along the other axis a tile's visible
# range starts.  Positions start at 0 on both sides.
#
# The third member is not causal: ``block_diffusion`` (a block length B)
# says the rows are two streams of one sequence, [noisy ; clean], each at
# positions 0 .. L-1 cut into blocks of B.  A noisy row sees the noisy rows
# of its own block and the clean rows of earlier blocks; a clean row the
# clean rows of its own and earlier blocks; no clean row sees a noisy one.
# Its four functions are below the causal three: the dense mask
# (``block_diffusion_mask``, the definition every path is held to), the
# three kinds of tile the streams' diagonals cross (``_block_mask``), and
# the order in which a q tile's K/V tiles (``_bd_key_tile``) and a K/V
# tile's q tiles (``_bd_query_tile``) are visited, none without a visible
# pair.
# ---------------------------------------------------------------------------


def _visible_mask(diff, offset, window: Optional[int]):
    """True where a pair is visible.  ``diff`` is row minus key inside the
    tile and ``offset`` the tile's first key position minus its first
    row's: the pair is ``diff - offset`` positions apart."""
    mask = diff >= offset
    if window is not None:
        mask = jnp.logical_and(mask, diff < offset + window)
    return mask


def _tile_kind(q0, bq: int, k0, bk: int, window: int):
    """(visited, visible) of the tile of rows ``q0 .. q0 + bq - 1`` and keys
    ``k0 .. k0 + bk - 1`` under a causal window: whether any pair of it is
    visible, and whether all are (no mask needed)."""
    q1, k1 = q0 + (bq - 1), k0 + (bk - 1)
    visited = jnp.logical_and(k0 <= q1, k1 > q0 - window)
    visible = jnp.logical_and(k1 <= q0, k0 > q1 - window)
    return visited, visible


def _first_key_block(iq, bq: int, bk: int, window: int):
    """The K/V block that holds the oldest key q tile ``iq`` sees."""
    return jnp.maximum(iq * bq - (window - 1), 0) // bk


def block_diffusion_mask(rows: int, block: int):
    """[rows, rows] bool, True where query row i sees key row j, for
    ``rows`` = 2 L rows [noisy ; clean] of L positions in blocks of
    ``block``: block-diagonal noisy on noisy, strictly earlier blocks noisy
    on clean, block-causal clean on clean, nothing clean on noisy."""
    half = rows // 2
    if rows != 2 * half or half % block:
        raise ValueError(
            f"block diffusion takes 2 L rows, L whole blocks of {block} "
            f"(got {rows} rows)")
    row = jnp.arange(rows)
    noisy = row < half
    blk = (row % half) // block
    q_noisy, k_noisy = noisy[:, None], noisy[None, :]
    qb, kb = blk[:, None], blk[None, :]
    return jnp.where(
        k_noisy, jnp.logical_and(q_noisy, qb == kb),
        jnp.where(q_noisy, kb < qb, kb <= qb))


def block_diffusion_tiles(rows: int, block: int) -> bool:
    """Whether the local kernels take ``rows`` = 2 L rows under blocks of
    ``block``: a block has to divide every chunk of a tile (powers of two
    from 128 down) and be smaller than the smallest tile (128: a tile of
    one block would have no earlier block for its noisy rows to see, and
    the kernels' order would still visit it), and L has to tile like any
    sequence."""
    half = rows // 2
    return (rows == 2 * half and block >= 1 and 64 % block == 0
            and half % min(128, half) == 0 and half % block == 0
            and half >= 8)


def _block_mask(shape, row0: int, key0: int, block: int, kind: str,
                transposed: bool = False):
    """True where a pair of a tile on a stream's diagonal is visible.  The
    tile holds rows ``row0 ..`` and keys ``key0 ..`` of one stretch of
    positions (static offsets inside it); ``kind`` is "same" (noisy on
    noisy: the key's block is the row's), "le" (clean on clean: not a
    later block) or "lt" (noisy on clean: an earlier block).  ``shape`` is
    [rows, keys], or [keys, rows] ``transposed`` (the backward's)."""
    rows_axis, keys_axis = (1, 0) if transposed else (0, 1)
    shift = block.bit_length() - 1              # block is a power of two

    def block_of(axis, first):
        pos = jax.lax.broadcasted_iota(jnp.int32, shape, axis) + first
        return jax.lax.shift_right_logical(pos, shift) if shift else pos

    rb, kb = block_of(rows_axis, row0), block_of(keys_axis, key0)
    return {"same": rb == kb, "le": kb <= rb, "lt": kb < rb}[kind]


def _bd_key_tile(iq, ik, n: int):
    """The forward's order under block diffusion, for q tile ``iq`` of the
    2 n square tiles [noisy ; clean] at step ``ik`` of its n + 1: (the
    K/V tile, whether the step has work, the q tile's place c in its
    stream, whether it is clean).  Step 0 is the tile's own diagonal tile
    (every row sees itself there, so no later step meets a row without a
    finite max); steps 1 .. c the clean tiles before it, wholly visible;
    step c + 1, for a noisy tile, clean tile c ("lt").  Steps past the
    last name the tile that is already resident."""
    clean = iq >= n
    c = jnp.where(clean, iq - n, iq)
    last = jnp.where(clean, c, c + 1)
    tile = jnp.where(ik == 0, iq,
                     n + jnp.maximum(jnp.minimum(ik, last) - 1, 0))
    return tile, ik <= last, c, clean


def _bd_query_tile(ik, iq, n: int):
    """The backward's order under block diffusion, for K/V tile ``ik`` of
    the 2 n at step ``iq`` of its 2 n: (the q tile, whether the step has
    work, how many q tiles a stream m holds from the K/V tile's place on,
    whether the K/V tile is clean).  A noisy K/V tile is seen by its own q
    tile alone (step 0, "same").  Clean tile c is seen by the noisy q
    tiles c .. n - 1 (steps 0 .. m - 1, the first "lt") and the clean q
    tiles c .. n - 1 (steps m .. 2 m - 1, the first "le").  Steps past the
    last name the tile that is already resident."""
    clean = ik >= n
    c = jnp.where(clean, ik - n, ik)
    m = n - c
    step = jnp.minimum(iq, 2 * m - 1)
    tile = jnp.where(clean,
                     jnp.where(step < m, c + step, n + c + step - m), c)
    return tile, jnp.where(clean, iq < 2 * m, iq == 0), m, clean


# The largest square tiles the local kernels take under block diffusion.
# Each call unrolls four kinds of tile (three diagonal kinds and the
# unmasked one), a chunk body for every 512 rows (forward) or 256 keys
# (backward) of each, and past these sizes that costs more than the fewer
# grid steps save.  Measured on the v5e at [1, 16384, 32 over 4, 128] bf16,
# blocks of 4, ms a call by device events (PERF.md, PR 39): forward 4096 /
# 2048 / 1024 / 512 tiles 24.17 / 9.38 / 10.32 / 17.26; backward 2048 /
# 1024 / 512 tiles 68.53 / 17.95 / 23.85.
_BD_FWD_TILE = 2048
_BD_BWD_TILE = 1024


def _window_block(window: int) -> int:
    """The largest block a windowed call takes: the largest power of two
    within the window (a row then computes at most two windows of keys),
    and no less than the 128 lanes the statistics' row needs."""
    return max(128, 1 << (window.bit_length() - 1))


def _head_lanes(x, j, heads: int):
    """True on the lanes of ``x`` [n, heads * d] that hold head ``j`` (a
    program id) of the ``heads`` whose block this is."""
    d = x.shape[-1] // heads
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.logical_and(lane >= j * d, lane < (j + 1) * d)


def _keep_head(x, j, heads: int):
    """``x`` [n, heads * d] with every head's lanes but head ``j``'s
    zeroed: a product that contracts over the lanes then sees head ``j``
    alone, and one that produces them leaves exact zeros in the others'.
    ``x`` itself where the block is one head."""
    if heads == 1:
        return x
    return jnp.where(_head_lanes(x, j, heads), x, jnp.zeros_like(x))


def _local_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, q_s, acc_s, m_s,
                  l_s, *, causal: bool, scale: float, fold_scale: bool,
                  rows: int, one_tile: bool, heads: int,
                  window: Optional[int] = None, key_blocks: int = 0,
                  bd: Optional[int] = None):
    """The local (non-ring) forward, grid (b, head group, iq, head, ik)
    with ik innermost: nothing to carry in and nothing to hand on, so
    (acc, m, l) are born in VMEM scratch at ik == 0 and die in the flush,
    which normalises, casts and writes ``out`` [1,bq,W] in the activation
    dtype and the logsumexp as one lane-dense row [1,1,1,bq].

    The blocks are ``W = heads * head_dim`` lanes of the projections' own
    [B, L, H*D] rows: one head where head_dim fills the 128 lanes, else
    the ``heads`` that together do.  A program works on ONE of them, grid
    axis ``head``, and the group's programs follow each other, so q, out
    and (one K/V block a sequence) k, v stay in VMEM from one to the next.
    Its score product contracts over all W lanes of q with the other
    heads' zeroed (on a 128-deep MXU the pass a 64-deep product costs
    too); its p @ v is taken W wide, the other heads' columns ride along
    in ``acc``, and the flush puts its own lanes into the block the group
    shares.  (Both heads unrolled in one program cost 12% of the forward
    and 62% of the backward, whatever the form: PERF.md, PR 29.)

    Positions start at 0 on both sides, so which tiles the diagonal
    crosses is known from (iq, ik) alone: only those build a mask.  A tile
    is worked through in chunks of ``rows`` query rows; on a square tile's
    diagonal a chunk stops at its own last key, so the masked triangle
    above it costs neither matmul nor exp.

    With a ``window`` the K/V axis of the grid is only as long as the
    blocks one q tile's window reaches (``key_blocks`` is the sequence's
    count): step ik works on block ``first + ik``, the first being the one
    that holds the tile's oldest visible key, and a step past the diagonal
    does nothing.  A tile the window's edge crosses is masked like one the
    diagonal crosses.

    Under block diffusion (``bd`` the block length, ``key_blocks`` the
    square tiles a stream has) the K/V axis is one step longer than a
    stream's tiles and ``_bd_key_tile`` says which tile a step works on:
    the q tile's own diagonal tile first, then the clean tiles it sees.  A
    chunk of rows on a diagonal takes the keys up to its own last row (of
    its own rows alone, noisy on noisy) and masks by blocks."""
    import jax.experimental.pallas as pl

    iq = pl.program_id(2)
    j = pl.program_id(3)
    ik = pl.program_id(4)
    nk = pl.num_programs(4)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]
    # The K/V block this step works on.
    kb = ik if window is None else ik + _first_key_block(iq, bq, bk, window)

    @pl.when(ik == 0)
    def _init():
        q = q_ref[0, :, :]
        if fold_scale:
            q = (q * scale).astype(q_s.dtype)
        q_s[...] = _keep_head(q, j, heads)
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    def _tile(straddles: bool, kind: Optional[str] = None):
        for r in range(0, bq, rows):
            # bq == bk puts a straddling tile on the diagonal (iq == ik):
            # rows r.. see no key past r + rows.
            keys = (min(bk, r + rows)
                    if straddles and bq == bk and window is None else bk)
            # Noisy on noisy: nor any key before r.
            key0 = r if kind == "same" else 0
            chunk = pl.ds(r, rows)
            s = jax.lax.dot_general(
                q_s[chunk, :], k_ref[0, key0:keys, :],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)       # [rows, keys]
            if not fold_scale:
                s = s * scale
            mask = None
            if kind is not None:
                mask = _block_mask(s.shape, r, key0, bd, kind)
            elif straddles:
                mask = _visible_mask(
                    jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                    - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1),
                    kb * bk - iq * bq - r, window)
            # Key 0 is visible to every row and ik == 0 comes first, so no
            # row meets a masked score with its max still at -1e30 (under
            # block diffusion a row's own key, in its first tile).  Under
            # a window a row's first block may hold none of its keys.
            _online_softmax_update(s, v_ref[0, key0:keys, :],
                                   acc_s.at[chunk], m_s.at[chunk],
                                   l_s.at[chunk], mask,
                                   rows_may_be_empty=window is not None)

    if bd is not None:
        _, _, c, clean = _bd_key_tile(iq, ik, key_blocks)
        noisy = jnp.logical_not(clean)
        first = ik == 0
        pl.when(jnp.logical_and(first, noisy))(lambda: _tile(True, "same"))
        pl.when(jnp.logical_and(first, clean))(lambda: _tile(True, "le"))
        pl.when(jnp.logical_and(ik >= 1, ik <= c))(lambda: _tile(False))
        pl.when(jnp.logical_and(ik == c + 1, noisy))(
            lambda: _tile(True, "lt"))
    elif not causal:
        _tile(False)
    elif window is not None:
        visited, visible = _tile_kind(iq * bq, bq, kb * bk, bk, window)
        visited = jnp.logical_and(visited, kb < key_blocks)
        pl.when(visible)(lambda: _tile(False))
        pl.when(jnp.logical_and(visited, jnp.logical_not(visible)))(
            lambda: _tile(True))
    elif one_tile:
        # The whole sequence is the diagonal's tile.  ik is 0: the cond is
        # there for interpret mode under shard_map (the CPU test path),
        # where TOP-LEVEL ref reads discharge to dynamic_slice whose vma
        # rule rejects varying-operand/unvarying-index mixes; inside a
        # cond the branch vma rule reconciles them (measured; jax 0.9
        # asks for an upstream issue).  Free on TPU: one true predicate.
        pl.when(ik == 0)(lambda: _tile(True))
    else:
        visited = (iq + 1) * bq - 1 >= ik * bk            # else: all masked
        visible = iq * bq >= (ik + 1) * bk - 1            # nothing masked
        pl.when(visible)(lambda: _tile(False))
        pl.when(jnp.logical_and(visited, jnp.logical_not(visible)))(
            lambda: _tile(True))

    @pl.when(ik == nk - 1)
    def _flush():
        l = jnp.maximum(l_s[...], 1e-30)
        out = (acc_s[...] / l).astype(o_ref.dtype)
        if heads > 1:
            # The group's heads share the block: the first writes it all,
            # each later one its own lanes.
            mine = jnp.logical_or(j == 0, _head_lanes(out, j, heads))
            out = jnp.where(mine, out, o_ref[0, :, :])
        o_ref[0, :, :] = out
        lse_ref[0, 0, :, :] = _column_to_row(m_s[...] + jnp.log(l))


def _column_to_row(col):
    """[n, 1] -> [1, n]: a transpose of the column spread over one lane
    tile, the form of the move Mosaic lowers (XLU)."""
    n = col.shape[0]
    return jnp.broadcast_to(col, (n, 128)).T[:1, :]


# Scoped VMEM the local forward and backward ask Mosaic for (half the 128
# MiB of a v5e or v6e core; Mosaic's default is 16), and what
# _forward_blocks and _backward_blocks let their own estimates reach.
_FWD_VMEM_LIMIT = 64 * 1024 * 1024
_FWD_VMEM_BUDGET = 56 * 1024 * 1024


def _chunk_rows(block: int, most: int = 512) -> int:
    """Query rows per chunk of a forward tile (keys per chunk of a
    backward tile, ``most`` 256).  512 measured best at every forward
    tile size (PERF.md, PR 25) and 256 at the backward's (PR 27): fewer
    pay the per-chunk work more often, more trim less of the diagonal's
    masked triangle."""
    rows = min(most, block)
    while block % rows:
        rows //= 2
    return rows


def _forward_blocks(lq: int, lk: int, head_dim: int, dtype
                    ) -> Tuple[int, int]:
    """(block_q, block_k) for the local forward, from the shape and VMEM
    alone, fitted to the two lengths.

    Square, and as large as fits: the whole sequence where it does.  The
    tile's vector work sets the pace (head_dim 64 leaves the MXU four
    fifths idle), so what a block shape can save is the work done once per
    chunk and key block (rescaling ``acc``, the exp of a [rows, 1] column
    that fills one lane in 128: both amortised over ``block_k``), the
    grid step, and the masked part of the tiles on the diagonal, which
    only a square tile can trim.  Measured on the v5e at b8 h16 L4096
    d64 bf16, ms a call (PERF.md, PR 25): 4096 x 4096 3.95, 2048 x 2048
    4.70, 1024 x 1024 5.25, 1024 x 2048 6.47, 512 x 1024 (no chunks) 6.49.

    The estimate was fitted to what Mosaic's own account needed at seven
    (head_dim, dtype, block) points, found by halving the limit until the
    compile failed: per row of the block, 5 KiB + 13 x head_dim x
    itemsize for the pipelined q/k/v/out blocks and the scratch, plus two
    f32 score chunks.  On [B, L, H*D] blocks (PR 29; MiB: d64 bf16 1024 /
    2048 / 4096 / 8192 blocks 9 / 21 / 35 / 77, d128 bf16 2048 / 4096 21
    / 34, d64 f32 2048 18) it is 0-8 MiB above each head_dim 64 point and
    up to 15 above head_dim 128's."""
    per_row = 5 * 1024 + 13 * head_dim * jnp.dtype(dtype).itemsize
    block = max(lq, lk)
    while (block * (per_row + 8 * _chunk_rows(block)) > _FWD_VMEM_BUDGET
           and block > 128):
        block //= 2
    return _fit_block(lq, block, dtype), _fit_block(lk, block, dtype)


def _scale_folds_exactly(scale: float, dtype) -> bool:
    """Whether ``q * scale`` in ``dtype`` loses nothing the product with
    the f32 score tile would have kept: a power of two always, any scale
    in f32 (to rounding)."""
    return (jnp.dtype(dtype).itemsize >= 4
            or math.frexp(scale)[0] == 0.5)


def _heads_per_program(heads: int, kv_heads: int, head_dim: int
                       ) -> Optional[int]:
    """How many heads one program of the local kernels takes from the
    [B, L, H*D] rows, from the shape alone: its block is that many
    head_dims wide and has to be whole 128-lane tiles, or the whole row.
    One where head_dim is a multiple of 128 (grouped queries then pick
    their kv head in the index map) or there is one head.  Otherwise q and
    kv heads have to pair one to one (a group's kv lanes are its q lanes):
    all of them where the row is no wider than 128 lanes, else 128 /
    head_dim where that is whole and divides the heads.  None for a shape
    that has no such block (H odd at head_dim 64, head_dim 96, grouped
    queries under 128): its callers fold the heads into the batch
    (``_rows_layout``) and come back with one head."""
    if head_dim % 128 == 0 or heads == kv_heads == 1:
        return 1
    if heads != kv_heads:
        return None
    if heads * head_dim <= 128:
        return heads
    per = 128 // head_dim
    return per if per * head_dim == 128 and heads % per == 0 else None


def _rows_layout(x, fold: bool):
    """[B, L, H, D] -> the local kernels' operand.  [B, L, H*D], the
    layout the projections write and a bitcast from here; or, for a shape
    ``_heads_per_program`` has no block for (``fold``), [B*H, L, D]: the
    heads transposed into the batch, one head a row."""
    b, l, h, d = x.shape
    if fold:
        return x.transpose(0, 2, 1, 3).reshape(b * h, l, d)
    return x.reshape(b, l, h * d)


def _heads_layout(x, shape, fold: bool):
    """``_rows_layout`` undone: -> ``shape`` = [B, L, H, D]."""
    b, l, h, d = shape
    if fold:
        return x.reshape(b, h, l, d).transpose(0, 2, 1, 3)
    return x.reshape(shape)


def _head_blocks(q, k, heads: int, block_q: int, block_k: int):
    """Of a local call's operands q [B,Lq,H*D], k [Bkv,Lk,Hkv*D]: (heads a
    block holds, the block's width W, q heads a kv head, q batch rows a kv
    batch row).  The blocks have to tile both sequences."""
    d = q.shape[2] // heads
    hkv = k.shape[2] // d
    per = _heads_per_program(heads, hkv, d)
    if q.shape[1] % block_q or k.shape[1] % block_k:
        raise ValueError(
            f"seq lens (q={q.shape[1]}, k={k.shape[1]}) must divide block "
            f"sizes ({block_q}, {block_k})")
    return per, per * d, heads // hkv, q.shape[0] // k.shape[0]


def _flash_local_call(q, k, v, *, heads, causal, scale, block_q, block_k,
                      rows=None, window=None, bd=None, eva=False):
    """The self-contained forward: q [B,Lq,H*D], k/v [Bkv,Lk,Hkv*D] (the
    projections' own rows, ``heads`` = H) -> (out [B,Lq,H*D] in q.dtype,
    lse [B,H,1,Lq] f32).  One pallas_call and nothing around it: no carry
    operand or result (``_flash_call`` keeps those, for the ring), no
    [B,H,L,D] array, statistics leave as a row, never as [..., 1].

    Grid (b, head group, q tile, head of the group, K/V block); the
    activations' blocks are (1, block, W) at (b, tile, head group), W =
    ``_heads_per_program`` head_dims: the head is a block index on the
    last dimension, and the lanes inside it where a block holds several.
    B is a multiple of Bkv where the caller folded grouped heads into the
    batch: q row b reads kv row b // (B / Bkv).

    Under a ``window`` the last grid axis is as long as the most blocks one
    q tile's window reaches, and the call's name is
    ``hvdt.kernel.flash_win_fwd``.  Under block diffusion (``bd``; square
    tiles that tile a stream) it is a stream's tiles and one, and the name
    ``hvdt.kernel.flash_bd_fwd``.  ``eva``: the causal call on the aligned
    windows of an EVA layer (``flash_attention_stats``), the same program
    under the name ``hvdt.kernel.eva_win_fwd``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, lq, _ = q.shape
    lk = k.shape[1]
    per, w, group, bgroup = _head_blocks(q, k, heads, block_q, block_k)
    kv_steps = lk // block_k
    if window is not None:
        # Blocks from a tile's oldest visible key to its diagonal, at most.
        kv_steps = max(
            ((i + 1) * block_q - 1) // block_k
            - max(i * block_q - (window - 1), 0) // block_k + 1
            for i in range(lq // block_q))
    if bd is not None:
        stream = lq // 2 // block_q             # square tiles a stream has
        kv_steps = stream + 1

    def kv_index(bb, hh, qq, jj, kk):
        if bd is not None:
            return (bb // bgroup, _bd_key_tile(qq, kk, stream)[0],
                    hh // group)
        if window is not None:
            kk = jnp.minimum(
                kk + _first_key_block(qq, block_q, block_k, window),
                lk // block_k - 1)
        if causal:
            # A tile past the diagonal is skipped: name the block that is
            # already resident, so nothing is fetched for it.
            kk = jnp.minimum(kk, ((qq + 1) * block_q - 1) // block_k)
        return (bb // bgroup, kk, hh // group)

    qspec = pl.BlockSpec((1, block_q, w),
                         lambda bb, hh, qq, jj, kk: (bb, qq, hh))
    kvspec = pl.BlockSpec((1, block_k, w), kv_index)
    lse_spec = pl.BlockSpec(
        (1, 1, 1, block_q),
        lambda bb, hh, qq, jj, kk: (bb, hh * per + jj, 0, qq))
    kw = _vma_kw(q, k, v)
    windowed = {} if window is None else dict(
        window=window, key_blocks=lk // block_k)
    if bd is not None:
        windowed = dict(bd=bd, key_blocks=stream)
    with kernel_scope("eva_win_fwd" if eva
                      else "flash_bd_fwd" if bd is not None
                      else "flash_fwd" if window is None
                      else "flash_win_fwd"):
        return pl.pallas_call(
            functools.partial(
                _local_kernel, causal=causal, scale=scale,
                fold_scale=_scale_folds_exactly(scale, q.dtype),
                rows=rows or _chunk_rows(block_q),
                one_tile=(lq, lk) == (block_q, block_k), heads=per,
                **windowed),
            grid=(b, heads // per, lq // block_q, per, kv_steps),
            in_specs=[qspec, kvspec, kvspec],
            out_specs=[qspec, lse_spec],
            out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype, **kw),
                       jax.ShapeDtypeStruct((b, heads, 1, lq), jnp.float32,
                                            **kw)),
            scratch_shapes=[pltpu.VMEM((block_q, w), q.dtype),
                            pltpu.VMEM((block_q, w), jnp.float32),
                            pltpu.VMEM((block_q, 1), jnp.float32),
                            pltpu.VMEM((block_q, 1), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_FWD_VMEM_LIMIT),
            interpret=_use_interpret(),
        )(q, k, v)


def _local_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref,
                      dk_ref, dv_ref, dq_s, dk_s, dv_s, *, causal: bool,
                      scale: float, fold_scale: bool, keys: int, heads: int,
                      window: Optional[int] = None,
                      bd: Optional[int] = None, stream: int = 0):
    """The local (non-ring) backward, grid (b, head group, ik, head, iq)
    with iq innermost: the K/V block stays while q, dO and the two row
    statistics stream past it, once for each head of the group.  dk/dv
    [bk, W] and the whole sequence's dq [Lq, W] accumulate in f32 VMEM
    scratch and leave once, in the activation dtype: dk/dv after the last
    q tile of the block's last head, dq after the last tile of the (b,
    head group) pair.

    The blocks are W = heads * head_dim lanes of the [B, L, H*D] rows, as
    the forward's, and a program works on one head of them.  One
    accumulator serves the group: head j's products take q, dO and k with
    the other heads' lanes zeroed, so the two that contract over the lanes
    (k q^T, v dO^T) see head j alone and the three that produce them (p^T
    dO, ds^T q, ds k) add exact zeros to the other heads' columns.

    The score tile is worked TRANSPOSED, s^T = k q^T [keys, rows]: the
    logsumexp and delta = rowsum(dO * out) then enter as lane-dense rows
    [1, rows] that broadcast along sublanes (no [rows, 1] column is ever
    built), dv = p^T dO and dk = ds^T q are plain products, and only dq =
    ds k contracts over the tile's first dimension.  One score product,
    one exp, five products in all per (rows, keys) pair.

    Causal work is trimmed as in the forward: only tiles the diagonal
    crosses build a mask, tiles before it (q rows that see none of the
    block's keys) do nothing, and on a square tile's diagonal a chunk of
    ``keys`` keys starts at its own first row.

    With a ``window`` the q axis of the grid is only as long as the tiles
    whose rows can see one K/V block: step iq works on q tile ``first +
    iq``, the first being the one the block's diagonal starts in, and a
    step past the window's edge does nothing.

    Under block diffusion (``bd`` the block length, ``stream`` the square
    tiles a stream has) the q axis is as long as the q tiles of both
    streams and ``_bd_query_tile`` says which a step works on; a chunk of
    keys on a diagonal takes the rows from its own first key on (its own
    rows alone, noisy on noisy) and masks by blocks."""
    import jax.experimental.pallas as pl

    ik = pl.program_id(2)
    j = pl.program_id(3)
    iq = pl.program_id(4)
    nk = pl.num_programs(2)
    nq = pl.num_programs(4)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]
    # The q tile this step works on.
    qt = iq if window is None else iq + (ik * bk) // bq
    if bd is not None:
        qt, _, seen, clean = _bd_query_tile(ik, iq, stream)
    nt = (((1,), (1,)), ((), ()))                 # a b^T
    nn = (((1,), (0,)), ((), ()))                 # a b
    tn = (((0,), (0,)), ((), ()))                 # a^T b
    f32 = jnp.float32

    @pl.when(jnp.logical_and(iq == 0, j == 0))
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

        @pl.when(ik == 0)
        def _():
            dq_s[...] = jnp.zeros_like(dq_s)

    def _tile(straddles: bool, kind: Optional[str] = None):
        q_all = q_ref[0, :, :]
        if fold_scale:
            q_all = (q_all * scale).astype(q_all.dtype)
        q_all = _keep_head(q_all, j, heads)
        do_all = _keep_head(do_ref[0, :, :], j, heads)
        row0 = pl.multiple_of(qt * bq, bq)
        for c in range(0, bk, keys):
            # bq == bk puts a straddling tile on the diagonal (iq == ik):
            # keys c.. are seen by no row before c.
            r = c if straddles and bq == bk and window is None else 0
            # Noisy on noisy: nor by any row from c + keys on.
            end = c + keys if kind == "same" else None
            chunk = pl.ds(c, keys)
            k = k_ref[0, chunk, :]
            q = q_all[r:end]
            do = do_all[r:end]
            st = jax.lax.dot_general(k, q, nt, preferred_element_type=f32)
            if not fold_scale:
                st = st * scale
            if kind is not None:
                st = jnp.where(_block_mask(st.shape, r, c, bd, kind, True),
                               st, _NEG_INF)
            elif straddles:
                # True = visible: row position >= key position.
                mask = _visible_mask(
                    jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
                    - jax.lax.broadcasted_iota(jnp.int32, st.shape, 0),
                    ik * bk + c - qt * bq - r, window)
                st = jnp.where(mask, st, _NEG_INF)
            # The saved logsumexp is finite, so a masked score's exp is an
            # exact 0 and p needs no second select.
            pt = jnp.exp(st - lse_ref[0, 0, :, r:end])       # [keys, rows]
            dpt = jax.lax.dot_general(v_ref[0, chunk, :], do, nt,
                                      preferred_element_type=f32)
            dst = (pt * (dpt - dl_ref[0, 0, :, r:end])).astype(q.dtype)
            dv_s[chunk, :] += jax.lax.dot_general(
                pt.astype(do.dtype), do, nn, preferred_element_type=f32)
            dk_s[chunk, :] += jax.lax.dot_general(
                dst, q, nn, preferred_element_type=f32)
            dq_s[pl.ds(row0 + r, (end or bq) - r), :] += jax.lax.dot_general(
                dst, _keep_head(k, j, heads), tn,
                preferred_element_type=f32)

    if bd is not None:
        noisy = jnp.logical_not(clean)
        pl.when(jnp.logical_and(noisy, iq == 0))(
            lambda: _tile(True, "same"))
        pl.when(jnp.logical_and(clean, iq == 0))(lambda: _tile(True, "lt"))
        pl.when(jnp.logical_and(clean, iq == seen))(
            lambda: _tile(True, "le"))
        pl.when(jnp.logical_and(
            jnp.logical_and(clean, iq < 2 * seen),
            jnp.logical_and(iq != 0, iq != seen)))(lambda: _tile(False))
    elif not causal:
        # ik >= 0 always: the cond is there for interpret mode under
        # shard_map (see _local_kernel's one-tile branch).
        pl.when(ik >= 0)(lambda: _tile(False))
    elif window is not None:
        visited, visible = _tile_kind(qt * bq, bq, ik * bk, bk, window)
        visited = jnp.logical_and(visited, qt < dq_s.shape[0] // bq)
        pl.when(visible)(lambda: _tile(False))
        pl.when(jnp.logical_and(visited, jnp.logical_not(visible)))(
            lambda: _tile(True))
    else:
        visited = (iq + 1) * bq - 1 >= ik * bk            # else: all masked
        visible = iq * bq >= (ik + 1) * bk - 1            # nothing masked
        pl.when(visible)(lambda: _tile(False))
        pl.when(jnp.logical_and(visited, jnp.logical_not(visible)))(
            lambda: _tile(True))

    @pl.when(jnp.logical_and(iq == nq - 1, j == heads - 1))
    def _flush():
        # s was computed from q * scale where that folds, so dk = ds^T
        # (q * scale) carries the factor already; dq = scale * ds k never.
        dk = dk_s[...] if fold_scale else dk_s[...] * scale
        dk_ref[0, :, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, :, :] = dv_s[...].astype(dv_ref.dtype)

        @pl.when(ik == nk - 1)
        def _():
            dq_ref[0, :, :] = (dq_s[...] * scale).astype(dq_ref.dtype)


# The backward's blocks stop here: at b8 h16 L4096 d64 bf16 on the v5e,
# ms a call by trace events (PERF.md, PR 27), 512 x 512 10.27, 1024 x 1024
# 8.51, 2048 x 2048 8.12, 4096 x 4096 9.16, though all four fit in VMEM.
_BWD_BLOCK_MOST = 2048


def _backward_blocks(lq: int, lk: int, head_dim: int, dtype
                     ) -> Optional[Tuple[int, int]]:
    """(block_q, block_k) for the local backward, from the shape and VMEM
    alone; None where the kernel cannot take the shape: the whole
    sequence's f32 dq, which it keeps in VMEM a head group wide, does not
    fit beside the smallest blocks (from seq 65,536 in bf16 up to
    head_dim 128).

    Square, and as large as fits up to ``_BWD_BLOCK_MOST``: a square tile
    is the one whose diagonal chunks can start at their own first row, and
    unlike the forward's the tile has no per-chunk column work for a larger
    block to amortise (the statistics are rows), so past 2048 nothing is
    saved.

    The estimate is fitted to what Mosaic's own account needed at eleven
    (seq, head_dim, dtype, block) points, found by halving the limit until
    the compile for the v5e failed (PR 29, on [B, L, H*D] blocks; MiB, seq
    4096: d64 bf16 512 / 1024 / 2048 / 4096 blocks 7 / 11 / 18 / 30, d128
    bf16 1024 / 2048 / 4096 11 / 17 / 23, d64 f32 1024 / 2048 16 / 27; d64
    bf16 seq 8192 at 2048 22, seq 16384 at 1024 23).  With W the block's
    lanes (head_dim in whole 128s): the dq scratch and its output block
    twice, and per row of the block (14 x itemsize + 12) x W bytes for the
    pipelined q/k/v/dO/dk/dv blocks, the dk/dv scratch, the head's q and
    dO and the products, plus two f32 score chunks; 0-2 MiB above each
    point (9 at d128's 4096)."""
    itemsize = jnp.dtype(dtype).itemsize
    lanes = -(-head_dim // 128) * 128
    whole = lq * lanes * (4 + 2 * itemsize)

    def need(block):
        return whole + block * ((14 * itemsize + 12) * lanes
                                + 8 * _chunk_rows(block, 256))

    block = min(max(lq, lk), _BWD_BLOCK_MOST)
    while need(block) > _FWD_VMEM_BUDGET and block > 128:
        block //= 2
    if need(block) > _FWD_VMEM_BUDGET:
        return None
    return _fit_block(lq, block, dtype), _fit_block(lk, block, dtype)


def _flash_local_bwd_call(q, k, v, do, lse, delta, *, heads, causal, scale,
                          block_q, block_k, keys=None, window=None,
                          bd=None, eva=False):
    """The self-contained backward: q, dO [B,Lq,H*D], k, v [Bkv,Lk,Hkv*D]
    (``heads`` = H; the forward's layout and blocks), lse and delta f32
    rows [B,H,1,Lq] -> (dq [B,Lq,H*D], dk, dv [B,Lk,H*D]) in the operands'
    dtypes, dk/dv per q head (a GQA caller sums its group).  One
    pallas_call, grid (b, head group, K/V block, head of the group, q
    tile): nothing f32 of the sequence's size, nothing with a trailing
    dimension of 1 and no [B,H,L,D] array goes in or comes out.

    Under a ``window`` the last grid axis is as long as the most q tiles
    that see one K/V block, and the call's name is
    ``hvdt.kernel.flash_win_bwd``.  Under block diffusion (``bd``; square
    tiles that tile a stream) it is the q tiles of both streams, and the
    name ``hvdt.kernel.flash_bd_bwd``; with ``eva`` (the forward's) it is
    ``hvdt.kernel.eva_win_bwd``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, lq, width = q.shape
    lk = k.shape[1]
    per, w, group, bgroup = _head_blocks(q, k, heads, block_q, block_k)
    q_steps = lq // block_q
    if window is not None:
        # Tiles from a block's diagonal to its last key's window edge.
        q_steps = max(
            min(((i + 1) * block_k + window - 2) // block_q,
                lq // block_q - 1) - (i * block_k) // block_q + 1
            for i in range(lk // block_k))

    stream = lq // 2 // block_q                 # square tiles a stream has

    def q_tile(kk, qq):
        if bd is not None:
            return _bd_query_tile(kk, qq, stream)[0]
        if window is not None:
            # Past the window's edge: the tile that is already resident.
            return jnp.minimum(
                qq + (kk * block_k) // block_q,
                jnp.minimum(((kk + 1) * block_k + window - 2) // block_q,
                            lq // block_q - 1))
        if causal:
            # q tiles before the diagonal are skipped: name the first one
            # that is not, so nothing is fetched for them.
            qq = jnp.maximum(qq, jnp.minimum((kk * block_k) // block_q,
                                             lq // block_q - 1))
        return qq

    qspec = pl.BlockSpec((1, block_q, w),
                         lambda bb, hh, kk, jj, qq: (bb, q_tile(kk, qq), hh))
    row = pl.BlockSpec((1, 1, 1, block_q),
                       lambda bb, hh, kk, jj, qq: (bb, hh * per + jj, 0,
                                                   q_tile(kk, qq)))
    kvspec = pl.BlockSpec(
        (1, block_k, w),
        lambda bb, hh, kk, jj, qq: (bb // bgroup, kk, hh // group))
    dqspec = pl.BlockSpec((1, lq, w), lambda bb, hh, kk, jj, qq: (bb, 0, hh))
    dkvspec = pl.BlockSpec((1, block_k, w),
                           lambda bb, hh, kk, jj, qq: (bb, kk, hh))
    kw = _vma_kw(q, k, v, do, lse, delta)
    windowed = {} if window is None else dict(window=window)
    if bd is not None:
        windowed = dict(bd=bd, stream=stream)
    with kernel_scope("eva_win_bwd" if eva
                      else "flash_bd_bwd" if bd is not None
                      else "flash_bwd" if window is None
                      else "flash_win_bwd"):
        return pl.pallas_call(
            functools.partial(
                _local_bwd_kernel, causal=causal, scale=scale,
                fold_scale=_scale_folds_exactly(scale, q.dtype),
                keys=keys or _chunk_rows(block_k, 256), heads=per,
                **windowed),
            grid=(b, heads // per, lk // block_k, per, q_steps),
            in_specs=[qspec, kvspec, kvspec, qspec, row, row],
            out_specs=[dqspec, dkvspec, dkvspec],
            out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype, **kw),
                       jax.ShapeDtypeStruct((b, lk, width), k.dtype, **kw),
                       jax.ShapeDtypeStruct((b, lk, width), v.dtype, **kw)),
            scratch_shapes=[pltpu.VMEM((lq, w), jnp.float32),
                            pltpu.VMEM((block_k, w), jnp.float32),
                            pltpu.VMEM((block_k, w), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_FWD_VMEM_LIMIT),
            interpret=_use_interpret(),
        )(q, k, v, do, lse, delta)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    block_diffusion: Optional[int] = None,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None) -> jax.Array:
    """Fused flash attention; layouts/API match
    parallel.ring_attention (q,k,v: [B, L, H, D]; GQA via fewer kv heads).

    Differentiable (pallas_call has no autodiff rule of its own): the
    forward is one Pallas call, q, k, v -> (out, logsumexp), and the
    backward another, the standard flash gradient with the score tile
    recomputed from the saved logsumexp, which makes it exact.  Both take
    and return [B, L, H*D], the layout the projections write and a
    reshape from here, so nothing stands around either but delta =
    rowsum(dO * out); only a shape ``_heads_per_program`` has no block for
    is transposed, heads into the batch.  No [B,H,Lq,Lk] array exists on
    either side (the property that makes long-context training fit in HBM
    at all).

    ``window`` (with ``causal``): query i sees keys i - window < j <= i,
    its own position counted.  Both calls then skip, by index map and
    ``pl.when``, every tile that lies wholly outside the band, as they skip
    the tiles above the diagonal, and take their blocks from the window
    (:func:`_window_block`), so a row computes at most two windows of keys;
    a window that reaches the whole sequence is no window.

    ``block_diffusion`` (a block length; in place of ``causal``): the rows
    are two streams of one sequence, [noisy ; clean], under
    :func:`block_diffusion_mask`.  Both calls visit only tiles with a
    visible pair, in square tiles that tile a stream
    (:func:`block_diffusion_tiles` says which shapes they take).

    ``block_q`` / ``block_k`` default to :func:`_forward_blocks`' choice
    for the shape; a test passes its own to meet a given tiling.
    """
    b, lq, h, d = q.shape
    lk = k.shape[1]
    if scale is None:
        scale = d ** -0.5
    auto_q, auto_k = _forward_blocks(lq, lk, d, q.dtype)
    if block_diffusion is not None:
        if window is not None or lq != lk or not block_diffusion_tiles(
                lq, block_diffusion) or _backward_blocks(
                    lq, lk, d, q.dtype) is None:
            raise ValueError(
                "block diffusion takes 2 L query and key rows, L whole 128s "
                f"and blocks of a power of two up to 64 (got {lq} and {lk} "
                f"rows, blocks of {block_diffusion}, window={window})")
        # Square tiles that tile a stream.
        tile = _fit_block(lq // 2, min(block_q or _BD_FWD_TILE,
                                       block_k or _BD_FWD_TILE, auto_q,
                                       auto_k), q.dtype, k.dtype, v.dtype)
        return _flash_attn_diff(q, k, v, False, float(scale), tile, tile,
                                None, block_diffusion)
    if window is not None:
        if not causal or window < 1:
            raise ValueError("a window is a causal mask's: causal=True and "
                             f"window >= 1 (got causal={causal}, "
                             f"window={window})")
        if window >= lk:
            window = None
        else:
            auto_q = _fit_block(lq, min(auto_q, _window_block(window)),
                                q.dtype)
            auto_k = _fit_block(lk, min(auto_k, _window_block(window)),
                                k.dtype, v.dtype)
    block_q = _fit_block(lq, block_q, q.dtype) if block_q else auto_q
    block_k = (_fit_block(lk, block_k, k.dtype, v.dtype)
               if block_k else auto_k)
    return _flash_attn_diff(q, k, v, causal, float(scale), block_q,
                            block_k, window, None)


def _flash_fwd_rows(q, k, v, causal, scale, block_q, block_k, window=None,
                    bd=None, eva=False):
    """Kernel forward returning (out [B,L,H,D], lse [B,H,1,Lq]): the
    logsumexp as the row the kernel writes and the backward reads."""
    b, lq, h, d = q.shape
    fold = _heads_per_program(h, k.shape[2], d) is None
    out, lse = _flash_local_call(
        *(_rows_layout(x, fold) for x in (q, k, v)),
        heads=1 if fold else h, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, window=window, bd=bd, eva=eva)
    return _heads_layout(out, q.shape, fold), lse.reshape(b, h, 1, lq)


def _flash_fwd_core(q, k, v, causal, scale, block_q, block_k, window=None):
    """Kernel forward returning (out [B,L,H,D], lse [B,H,Lq])."""
    out, lse = _flash_fwd_rows(q, k, v, causal, scale, block_q, block_k,
                               window)
    return out, lse[:, :, 0, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attn_diff(q, k, v, causal, scale, block_q, block_k, window, bd):
    out, _ = _flash_fwd_rows(q, k, v, causal, scale, block_q, block_k,
                             window, bd)
    return out


def _flash_attn_fwd(q, k, v, causal, scale, block_q, block_k, window, bd):
    out, lse = _flash_fwd_rows(q, k, v, causal, scale, block_q, block_k,
                               window, bd)
    return out, (q, k, v, out, lse)


def _flash_attn_bwd(causal, scale, block_q, block_k, window, bd, res, do,
                    dlse=None, eva=False):
    """The local backward: one Pallas call (``_flash_local_bwd_call``) on
    the forward's operand layout, delta = rowsum(dO * out) computed beside
    it as a row.  A test's own forward blocks bound the backward's too, so
    a given tiling is met on both sides.  Only a sequence whose f32 dq
    does not fit in VMEM (``_backward_blocks`` is None) takes the
    blockwise XLA backward (under block diffusion ``flash_attention`` has
    refused it).  ``dlse`` [B,H,1,Lq] is the logsumexp's cotangent where
    that is an output too (``flash_attention_stats``): d lse / d s = p, so
    the score's cotangent p (dp - delta) takes delta - dlse for delta."""
    q, k, v, out, lse = res
    b, lq, h, d = q.shape
    lk, hkv = k.shape[1], k.shape[2]
    blocks = _backward_blocks(lq, lk, d, q.dtype)
    if blocks is None:
        return _flash_bwd_blockwise(causal, scale, block_q, block_k,
                                    (q, k, v, out, lse[:, :, 0, :]), do,
                                    window)
    delta = jnp.einsum("bqhd,bqhd->bhq", do, out,
                       preferred_element_type=jnp.float32)[:, :, None, :]
    if dlse is not None:
        delta = delta - dlse
    fold = _heads_per_program(h, hkv, d) is None
    heads = 1 if fold else h
    # Under block diffusion the (square) tiles have to tile a stream.
    streams = 1 if bd is None else 2
    if bd is not None:
        blocks = tuple(min(x, _BD_BWD_TILE) for x in blocks)
    dq, dk, dv = _flash_local_bwd_call(
        *(_rows_layout(x, fold) for x in (q, k, v, do)),
        *(x.reshape(b * h // heads, heads, 1, lq) for x in (lse, delta)),
        heads=heads, causal=causal, scale=scale,
        block_q=_fit_block(lq // streams, min(block_q, blocks[0]), q.dtype),
        block_k=_fit_block(lk // streams, min(block_k, blocks[1]), k.dtype,
                           v.dtype),
        window=window, bd=bd, eva=eva)
    dq, dk, dv = (_heads_layout(x, (b, x.shape[1], h, d), fold)
                  for x in (dq, dk, dv))
    if h != hkv:
        # dk/dv leave per q head: sum each kv head's group, in f32.
        dk, dv = (x.reshape(b, lk, hkv, h // hkv, d).astype(jnp.float32)
                  .sum(3).astype(x.dtype) for x in (dk, dv))
    return dq, dk, dv


def _flash_bwd_blockwise(causal, scale, block_q, block_k, res, do,
                         window=None):
    """The flash gradient recomputed BLOCKWISE over K in plain XLA, for
    the sequence the kernel cannot hold (``_flash_attn_bwd``): two nested
    scans over <= 512-wide tiles, a whole-sequence f32 dq as the carry.
    ``res`` is (q, k, v, out [B,L,H,D], lse [B,H,Lq])."""
    q, k, v, out, lse = res
    b, lq, h, d = q.shape
    lk, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    # Backward tiles bounded independently of the forward kernel's
    # VMEM-tuned blocks: the [B,H,tq,blk] f32 score tile is the
    # backward's working set, so cap it ADAPTIVELY by B*H — at large
    # batch x heads a fixed 512x512 tile is a quarter-GB per
    # intermediate and XLA starts spilling (measured: BERT-Large
    # seq 4096 collapsed from 12.3k to 6.5k tok/s when batch doubled
    # the tile to 256 MB).  The budget also halves the 134 MB batch-8
    # config's tiles; measured harmless there (12.9k capped vs 12.3k
    # uncapped — smaller tiles cost nothing on this workload).
    blk = _fit_block(lk, min(block_k, 512), jnp.float32)
    tq = _fit_block(lq, min(block_q, 512), jnp.float32)
    tile_budget = 96 * 1024 * 1024                       # bytes, f32 tile
    while b * h * tq * blk * 4 > tile_budget and max(tq, blk) > 128:
        if blk >= tq and blk > 128:
            blk = _fit_block(lk, blk // 2, jnp.float32)
        else:
            tq = _fit_block(lq, tq // 2, jnp.float32)
    nblk, ntq = lk // blk, lq // tq

    f32 = jnp.float32
    # delta_i = sum_d do_i * o_i (rowsum term of dS), f32-accumulated
    # without materializing whole-sequence f32 copies of do/out — tiles
    # are upcast inside tile() instead (the [B,Lq,*,D] f32 copies would
    # cost ~3x 128 MB at the documented bf16 seq-8192 config).
    delta = jnp.einsum("bqhd,bqhd->bqh", do, out,
                       preferred_element_type=f32)

    from ..parallel.sharding import pcast_to_union

    def _v(x):
        return pcast_to_union(x, q, k, v, do)

    delta, lse = _v(delta), _v(lse)

    def tile(i, j, ks, vs):
        """Grad contributions of (q tile j) x (k block i)."""
        q_t = jax.lax.dynamic_slice_in_dim(q, j * tq, tq, 1).astype(f32)
        do_t = jax.lax.dynamic_slice_in_dim(do, j * tq, tq, 1).astype(f32)
        dl_t = jax.lax.dynamic_slice_in_dim(delta, j * tq, tq, 1)
        lse_t = jax.lax.dynamic_slice_in_dim(lse, j * tq, tq, 2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_t, ks) * scale
        if causal:
            q_pos = j * tq + jnp.arange(tq)
            k_pos = i * blk + jnp.arange(blk)
            mask = _visible_mask(q_pos[:, None] - k_pos[None, :], 0, window)
            s = jnp.where(mask[None, None], s, _NEG_INF)
        p = jnp.exp(s - lse_t[..., None])                # [B,H,tq,blk]
        dv_b = jnp.einsum("bhqk,bqhd->bkhd", p, do_t)
        dp = jnp.einsum("bqhd,bkhd->bhqk", do_t, vs)
        ds = p * (dp - dl_t.transpose(0, 2, 1)[..., None]) * scale
        dq_t = jnp.einsum("bhqk,bkhd->bqhd", ds, ks)
        dk_b = jnp.einsum("bhqk,bqhd->bkhd", ds, q_t)
        return dq_t, dk_b, dv_b

    def k_block(dq_acc, i):
        ks = jax.lax.dynamic_slice_in_dim(k, i * blk, blk, 1).astype(f32)
        vs = jax.lax.dynamic_slice_in_dim(v, i * blk, blk, 1).astype(f32)
        if group > 1:
            ks = jnp.repeat(ks, group, axis=2)
            vs = jnp.repeat(vs, group, axis=2)

        def q_tile(carry, j):
            dq_acc, dk_b, dv_b = carry

            def compute(args):
                dq_acc, dk_b, dv_b = args
                dq_t, dk_t, dv_t = tile(i, j, ks, vs)
                dq_acc = jax.lax.dynamic_update_slice_in_dim(
                    dq_acc,
                    jax.lax.dynamic_slice_in_dim(dq_acc, j * tq, tq, 1)
                    + dq_t, j * tq, 1)
                return dq_acc, dk_b + dk_t, dv_b + dv_t

            if causal:
                # Causal pruning (the forward kernel's flops halving,
                # mirrored): a q tile strictly above this K block's
                # first row is fully masked — skip its four einsums.
                visible = (j + 1) * tq - 1 >= i * blk
                dq_acc, dk_b, dv_b = jax.lax.cond(
                    visible, compute, lambda args: args,
                    (dq_acc, dk_b, dv_b))
            else:
                dq_acc, dk_b, dv_b = compute((dq_acc, dk_b, dv_b))
            return (dq_acc, dk_b, dv_b), None

        zeros_kv = _v(jnp.zeros((b, blk, h, d), f32))
        (dq_acc, dk_b, dv_b), _ = jax.lax.scan(
            q_tile, (dq_acc, zeros_kv, zeros_kv), jnp.arange(ntq))
        if group > 1:
            dk_b = dk_b.reshape(b, blk, hkv, group, d).sum(3)
            dv_b = dv_b.reshape(b, blk, hkv, group, d).sum(3)
        return dq_acc, (dk_b, dv_b)

    dq0 = _v(jnp.zeros((b, lq, h, d), f32))
    dq, (dk_blocks, dv_blocks) = jax.lax.scan(k_block, dq0,
                                              jnp.arange(nblk))
    dk = dk_blocks.transpose(1, 0, 2, 3, 4).reshape(b, lk, hkv, d)
    dv = dv_blocks.transpose(1, 0, 2, 3, 4).reshape(b, lk, hkv, d)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


_flash_attn_diff.defvjp(_flash_attn_fwd, _flash_attn_bwd)


def flash_attention_stats(q: jax.Array, k: jax.Array, v: jax.Array, *,
                          scale: Optional[float] = None):
    """Causal flash attention that also returns its logsumexp, both
    differentiable: ``(out [B,L,H,D], lse [B,H,L] f32)``.  What a caller
    needs to join this softmax with another over further keys (EVA's
    summaries, ``ops/eva.py``: the rows are an EVA layer's aligned windows,
    one a batch row).  The two local calls of :func:`flash_attention`, with
    their blocks, under ``hvdt.kernel.eva_win_fwd`` / ``eva_win_bwd``."""
    d = q.shape[-1]
    block_q, block_k = _forward_blocks(q.shape[1], k.shape[1], d, q.dtype)
    if _backward_blocks(q.shape[1], k.shape[1], d, q.dtype) is None:
        raise ValueError(f"no backward blocks for {q.shape[1]} rows")
    out, lse = _flash_attn_stats(
        q, k, v, float(d ** -0.5 if scale is None else scale), block_q,
        block_k)
    return out, lse[:, :, 0, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_attn_stats(q, k, v, scale, block_q, block_k):
    return _flash_fwd_rows(q, k, v, True, scale, block_q, block_k, eva=True)


def _flash_attn_stats_fwd(q, k, v, scale, block_q, block_k):
    out, lse = _flash_fwd_rows(q, k, v, True, scale, block_q, block_k,
                               eva=True)
    return (out, lse), (q, k, v, out, lse)


def _flash_attn_stats_bwd(scale, block_q, block_k, res, cotangents):
    do, dlse = cotangents
    return _flash_attn_bwd(True, scale, block_q, block_k, None, None, res,
                           do, dlse, eva=True)


_flash_attn_stats.defvjp(_flash_attn_stats_fwd, _flash_attn_stats_bwd)


def flash_block_update(q: jax.Array, k_blk: jax.Array, v_blk: jax.Array,
                       acc: jax.Array, row_max: jax.Array,
                       row_sum: jax.Array, *, q_offset, k_offset,
                       causal: bool, scale: float,
                       block_q: int = 512, block_k: int = 1024
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One ring step in ring-attention layout.

    q/acc: [B, Lq, H, D]; k_blk/v_blk: [B, Lk, Hkv, D];
    row_max/row_sum: [B, H, Lq].  ``q_offset``/``k_offset`` are the global
    positions of the local shards (traced values are fine — they ride the
    scalar-prefetch arguments).
    """
    b, lq, h, d = q.shape
    block_q = _fit_block(lq, block_q, q.dtype)
    block_k = _fit_block(k_blk.shape[1], block_k, k_blk.dtype, v_blk.dtype)
    qt = q.transpose(0, 2, 1, 3)
    kt = k_blk.transpose(0, 2, 1, 3)
    vt = v_blk.transpose(0, 2, 1, 3)
    acc_t = acc.transpose(0, 2, 1, 3).astype(jnp.float32)
    m_t = row_max[..., None].astype(jnp.float32)
    l_t = row_sum[..., None].astype(jnp.float32)
    acc_t, m_t, l_t = _flash_call(
        qt, kt, vt, acc_t, m_t, l_t, q_offset, k_offset, causal=causal,
        scale=scale, block_q=block_q, block_k=block_k)
    return (acc_t.transpose(0, 2, 1, 3), m_t[..., 0], l_t[..., 0])


def _dq_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, dl_ref,
               lse_ref, dq_ref, dq_s, *, causal: bool, scale: float):
    """Grid (b, h, iq, ik), ik innermost: dq tile accumulated in VMEM
    scratch while K/V/dO stream; flushed at the last ik.  Standard flash
    backward dq pass with the saved logsumexp making the score recompute
    exact."""
    import jax.experimental.pallas as pl

    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    bq, bk = q_ref.shape[2], k_ref.shape[2]

    @pl.when(ik == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    def _compute():
        q = q_ref[0, 0, :, :]
        kb = k_ref[0, 0, :, :]
        vb = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse_ref[0, 0, :, :])
        if causal:
            q_pos = (qo_ref[0] + iq * bq
                     + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0))
            k_pos = (ko_ref[0] + ik * bk
                     + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1))
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dl_ref[0, 0, :, :]) * scale
        dq_s[...] += jax.lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        last_q = qo_ref[0] + iq * bq + (bq - 1)
        first_k = ko_ref[0] + ik * bk
        pl.when(last_q >= first_k)(_compute)
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _flush():
        dq_ref[0, 0, :, :] = dq_s[...]


def _dkv_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, dl_ref,
                lse_ref, dk_ref, dv_ref, dk_s, dv_s, *, causal: bool,
                scale: float):
    """Grid (b, h, ik, iq), iq innermost: dk/dv tiles accumulated in VMEM
    scratch while Q/dO stream past the resident K/V block."""
    import jax.experimental.pallas as pl

    ik, iq = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)
    bq, bk = q_ref.shape[2], k_ref.shape[2]

    @pl.when(iq == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    def _compute():
        q = q_ref[0, 0, :, :]
        kb = k_ref[0, 0, :, :]
        vb = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse_ref[0, 0, :, :])
        if causal:
            q_pos = (qo_ref[0] + iq * bq
                     + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0))
            k_pos = (ko_ref[0] + ik * bk
                     + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1))
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        pb = p.astype(do.dtype)
        dv_s[...] += jax.lax.dot_general(
            pb, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dl_ref[0, 0, :, :]) * scale
        dk_s[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        last_q = qo_ref[0] + iq * bq + (bq - 1)
        first_k = ko_ref[0] + ik * bk
        pl.when(last_q >= first_k)(_compute)
    else:
        _compute()

    @pl.when(iq == nq - 1)
    def _flush():
        dk_ref[0, 0, :, :] = dk_s[...]
        dv_ref[0, 0, :, :] = dv_s[...]


def flash_grad_block(q, k, v, do, out, lse, *, q_offset=0, k_offset=0,
                     causal: bool = True, scale: Optional[float] = None,
                     block_q: int = 512, block_k: int = 512,
                     delta: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pallas flash backward for one (Q block x K/V block) pair.

    The gradient counterpart of :func:`flash_block_update` — the piece
    that makes the Pallas ring-attention path trainable (VERDICT r2 #4):
    ``parallel/ring_attention.py`` calls it once per ring step with the
    visiting K/V block and its global offset, accumulating dK/dV that
    travel with the block.  Also usable as a whole-sequence flash
    backward (q_offset=k_offset=0).

    Layout matches the framework: q/do/out [B, Lq, H, D]; k/v
    [B, Lk, Hkv, D] (GQA: dk/dv are group-summed here); lse [B, H, Lq].
    Returns (dq, dk, dv) in f32.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, lq, h, d = q.shape
    lk, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    if scale is None:
        scale = d ** -0.5
    block_q = _fit_block(lq, block_q, q.dtype)
    block_k = _fit_block(lk, block_k, k.dtype, v.dtype)

    if delta is None:
        delta = jnp.einsum("bqhd,bqhd->bqh", do, out,
                           preferred_element_type=jnp.float32)  # [B,Lq,H]
        delta = delta.transpose(0, 2, 1)                        # [B,H,Lq]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)
    dl = delta[..., None]                                       # [B,H,Lq,1]
    lse_c = lse[..., None]                                      # [B,H,Lq,1]

    kw = _vma_kw(q, k, v, do, lse)

    qspec = pl.BlockSpec((1, 1, block_q, d),
                         lambda bb, hh, qq, kk, *_: (bb, hh, qq, 0))
    kvspec = pl.BlockSpec((1, 1, block_k, d),
                          lambda bb, hh, qq, kk, *_: (bb, hh // group, kk, 0))
    col_q = pl.BlockSpec((1, 1, block_q, 1),
                         lambda bb, hh, qq, kk, *_: (bb, hh, qq, 0))

    with kernel_scope("flash_dq"):
        dq, = pl.pallas_call(
            functools.partial(_dq_kernel, causal=causal, scale=float(scale)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(b, h, lq // block_q, lk // block_k),
                in_specs=[qspec, kvspec, kvspec, qspec, col_q, col_q],
                out_specs=[qspec],
                scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)]),
            out_shape=(jax.ShapeDtypeStruct((b, h, lq, d), jnp.float32,
                                            **kw),),
            interpret=_use_interpret(),
        )(jnp.atleast_1d(q_offset).astype(jnp.int32),
          jnp.atleast_1d(k_offset).astype(jnp.int32),
          qt, kt, vt, dot, dl, lse_c)

    # dkv pass: grid loops K blocks outer, Q blocks inner.  BlockSpec
    # index maps receive (bb, hh, kk, qq).
    qspec2 = pl.BlockSpec((1, 1, block_q, d),
                          lambda bb, hh, kk, qq, *_: (bb, hh, qq, 0))
    kvspec2 = pl.BlockSpec((1, 1, block_k, d),
                           lambda bb, hh, kk, qq, *_:
                           (bb, hh // group, kk, 0))
    kvout2 = pl.BlockSpec((1, 1, block_k, d),
                          lambda bb, hh, kk, qq, *_: (bb, hh, kk, 0))
    col_q2 = pl.BlockSpec((1, 1, block_q, 1),
                          lambda bb, hh, kk, qq, *_: (bb, hh, qq, 0))
    with kernel_scope("flash_dkv"):
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, causal=causal, scale=float(scale)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(b, h, lk // block_k, lq // block_q),
                in_specs=[qspec2, kvspec2, kvspec2, qspec2, col_q2, col_q2],
                out_specs=[kvout2, kvout2],
                scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                                pltpu.VMEM((block_k, d), jnp.float32)]),
            out_shape=(jax.ShapeDtypeStruct((b, h, lk, d), jnp.float32, **kw),
                       jax.ShapeDtypeStruct((b, h, lk, d), jnp.float32, **kw)),
            interpret=_use_interpret(),
        )(jnp.atleast_1d(q_offset).astype(jnp.int32),
          jnp.atleast_1d(k_offset).astype(jnp.int32),
          qt, kt, vt, dot, dl, lse_c)

    dq = dq.transpose(0, 2, 1, 3)                               # [B,Lq,H,D]
    dk = dk.transpose(0, 2, 1, 3)                               # [B,Lk,H,D]
    dv = dv.transpose(0, 2, 1, 3)
    if group > 1:
        dk = dk.reshape(b, lk, hkv, group, d).sum(3)
        dv = dv.reshape(b, lk, hkv, group, d).sum(3)
    return dq, dk, dv


_SUBLANES = 8                   # rows of a float32 vector register
_INVERSE_LANES = 128            # matrices a program: one register's lanes
_INVERSE_MOST = 64              # [C, C, lanes] in and out, double-buffered


def unit_lower_inverse_tiles(c: int) -> bool:
    """Whether :func:`unit_lower_inverse_slabs` takes matrices of c x c:
    whole groups of 8 rows, and blocks that fit VMEM twice over (C = 64 is
    2 MiB a block, 8 MiB in all)."""
    return c % _SUBLANES == 0 and 0 < c <= _INVERSE_MOST


def _inverse_kernel(a_ref, t_ref, *, guarded: bool):
    """``(I + a)^-1`` for ``lanes`` strictly lower triangular matrices,
    ``a_ref`` [row i, column k, matrix] -> ``t_ref`` [row k, column j,
    matrix]: row i = e_i - sum over k < i of a[i, k] (row k), all C row
    steps on the block in VMEM.  A row is a [C, lanes] slab, its columns
    on the sublanes, so a[i, k] of all the matrices is one sublane spread
    over the columns and a step is a multiply and a subtract on whole
    registers; no product goes to the MXU, everything is float32.

    Rows go in groups of 8.  A row of group s is 0 right of column
    8 (s + 1), and so is every row it reads: it keeps 8 (s + 1) columns
    and takes as many of each row before it, which is exact (what is
    skipped is 0).  A group's rows are zeroed before its first row step
    and the sum runs to the group's end, 8 steps a trip of its loop: a row
    then reads its group's later rows as 0 times the 0 that ``a`` holds
    there, and no bound depends on the row.

    The steps are on whole [8 (s + 1), lanes] slabs, which Mosaic splits
    into registers, and the sum is a loop: some 500 operations to trace
    and lower.  Unrolled register by register, each row taking from row k
    only the column groups up to k's own (half the multiplies), it was
    3,300, hardly faster (0.40 ms a call against 0.48: the call is bound
    by its bytes) and, with the branch below, 29 s more of set-up a run
    (PERF.md, PR 34).

    ``guarded`` puts all of it in a branch that is always taken, for
    interpret mode under shard_map (the CPU test path): a loop that
    carries a ref was typed when the kernel was traced, without the
    operands' varying axes, and bound bare it is refused on them; inside
    a branch it is not bound again (as ``_local_kernel``'s one_tile case).
    Not on the TPU, where nothing is bound again and the branch costs
    seconds to lower."""
    from jax.experimental import pallas as pl

    c, _, lanes = a_ref.shape
    g = _SUBLANES

    def group(s):
        width = (s + 1) * g
        col = jax.lax.broadcasted_iota(jnp.int32, (width, lanes), 0)
        t_ref[s * g:width] = jnp.zeros((g, c, lanes), jnp.float32)

        def row(i, carry):
            def steps(trip, acc):
                for step in range(g):
                    k = trip * g + step
                    acc = acc - a_ref[i, pl.ds(k, 1), :] * t_ref[
                        k, :width, :]
                return acc

            t_ref[i, :width, :] = jax.lax.fori_loop(
                0, s + 1, steps, jnp.where(col == i, 1.0, 0.0))     # e_i
            return carry

        jax.lax.fori_loop(s * g, width, row, 0)

    def groups():
        for s in range(c // g):
            group(s)

    if guarded:
        pl.when(pl.program_id(0) >= 0)(groups)
    else:
        groups()


def unit_lower_inverse_slabs(cols: jax.Array) -> jax.Array:
    """``(I + a)^-1`` by forward substitution, one Mosaic call: ``cols``
    [row, column, matrix] float32, strictly lower triangular a matrix,
    any count of matrices (padded here to whole blocks of
    ``_INVERSE_LANES``; a padded matrix is 0 and its inverse I) -> the
    inverses in the same layout.  Also [row, group, column, matrix] (the
    chunk kernels' slabs: a group is a value head of its key head), each
    group a set of slabs of its own.  ``a`` is read once and the inverse
    written once; the rows between never leave VMEM.  The caller checks
    :func:`unit_lower_inverse_tiles` first."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    shape = cols.shape
    c, m = shape[0], shape[-1]
    cols = cols.reshape(c, -1, c, m)
    lanes = _INVERSE_LANES
    pad = (-m) % lanes
    if pad:
        cols = jnp.pad(cols, ((0, 0),) * 3 + ((0, pad),))
    spec = pl.BlockSpec((c, None, c, lanes), lambda gg, mm: (0, gg, 0, mm))
    with kernel_scope("gdn_inverse"):
        t = pl.pallas_call(
            functools.partial(_inverse_kernel, guarded=_use_interpret()),
            grid=(cols.shape[1], (m + pad) // lanes),
            in_specs=[spec], out_specs=spec,
            out_shape=jax.ShapeDtypeStruct(cols.shape, jnp.float32,
                                           **_vma_kw(cols)),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=_use_interpret(),
        )(cols)
    return (t[..., :m] if pad else t).reshape(shape)


# The Gated DeltaNet scan's chunk-local passes (ops/gated_delta.py states
# the mathematics and the rule; these are its Mosaic schedule).  A program
# is (sequence, block of chunks, key head): q and k of the head and v of its
# R value heads are column blocks of the projection's own [B, L, 2 Kd + Vd]
# rows, a chunk's [C, C] matrices of the R value heads lie side by side on
# the lanes ([C, R C]: whole lane tiles at C 64, R 2), and the per-token
# scalars (the cumulative log-decay and beta of each value head) come as
# rows [8, C] a (chunk, key head) that a program turns into columns itself.

_GDN_CHUNKS = 16                # chunks a program, at most
_GDN_TRIP = 4                   # of them a trip of the program's loop
_GDN_VMEM = 48 << 20


def gdn_chunk_tiles(rows: int, dims, chunk: int) -> bool:
    """Whether the chunk kernels take rows of ``rows`` tokens with ``dims``
    = (Hk, Hv, dk, dv): whole chunks of 64, heads of whole 128-lane tiles
    with dk = dv (so that a key head's R value heads are one column block),
    R value heads a key head whose [C, R C] matrices fill whole lane tiles
    and whose 2 R scalar rows fit 8 sublanes."""
    hk, hv, dk, dv = dims
    r = hv // hk
    return (chunk == 64 and rows % chunk == 0 and hv == hk * r
            and dk % 128 == 0 and dv == dk and (r * chunk) % 128 == 0
            and 2 * r <= _SUBLANES)


def _gdn_blocks(qkv, dims, chunk: int):
    """For the grid (B, N / nb, Hk): the chunks a program nb;
    ``column(width, first)``, the block spec of a program's tokens by
    ``width`` columns of token-major rows, column block ``first + h`` for
    key head h; and q, k and v of a key head so on the rows ``qkv``
    [B, L, 2 Kd + Vd] (v its R value heads wide)."""
    import jax.experimental.pallas as pl

    hk, hv, dk, dv = dims
    r = hv // hk
    nb = math.gcd(qkv.shape[1] // chunk, _GDN_CHUNKS)

    def column(width, first=0):
        return pl.BlockSpec((None, nb * chunk, width),
                            lambda b, j, h: (b, j, first + h))

    return nb, column, (column(dk), column(dk, hk),
                        column(r * dv, 2 * hk * dk // (r * dv)))


def _gdn_per_chunk(nb: int):
    """A BlockSpec maker for arrays [B, N, Hk, ...]: ``nb`` chunks of one
    key head a program."""
    import jax.experimental.pallas as pl

    def spec(*tail):
        zeros = (0,) * len(tail)
        return pl.BlockSpec((None, nb, None) + tail,
                            lambda b, j, h: (b, j, h) + zeros)
    return spec


def _gdn_call(kernel, grid, in_specs, out_specs, out_shape, operands, part):
    """One of the three calls (``part``: before, after, bwd), all on the
    grid (B, N / nb, Hk) with every program independent."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    vma = _vma_kw(*operands)
    out_shape = [jax.ShapeDtypeStruct(s, d, **vma) for s, d in out_shape]
    with kernel_scope("gdn_chunk_before" if part == "before"
                      else "gdn_chunk_after" if part == "after"
                      else "gdn_chunk_bwd"):
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",) * len(grid),
                vmem_limit_bytes=_GDN_VMEM),
            interpret=_use_interpret(),
        )(*operands)


def _gdn_unit_rows(x):
    """x [C, d] -> (x / |x| in float32, 1 / |x| [C, 1]), eps 1e-6 under
    the root."""
    x = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    return x * r, r


def _dot_nt(x, y):                      # x y^T, float32 out
    return jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_tn(x, y):                      # x^T y, float32 out
    return jax.lax.dot_general(x, y, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _gdn_ratio(gc_col, gc_row):
    """gamma_i / gamma_j for j <= i and 0 above the diagonal, [C, C], and
    the strict lower triangle's mask."""
    c = gc_col.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    ratio = jnp.exp(jnp.where(j <= i, gc_col - gc_row, -jnp.inf))
    return ratio, j < i


def _in_a_taken_branch(run):
    """``run()``, in interpret mode inside a branch that is always taken:
    under shard_map a kernel's reads of its refs were typed when it was
    traced, without the operands' varying axes, and bound bare they are
    refused on them; inside a branch they are not bound again (as
    ``_inverse_kernel``'s ``guarded``).  Not on the TPU, where nothing is
    bound again and the branch costs seconds to lower."""
    import jax.experimental.pallas as pl

    if _use_interpret():
        pl.when(pl.program_id(0) >= 0)(run)
    else:
        run()


def _gdn_chunks_loop(nb: int, body):
    """``body(c)`` for the program's chunks, ``_GDN_TRIP`` of them a trip
    of the loop: a chunk is one serial chain of small products, lane sums
    and transposes, the chunks' chains are independent, and Mosaic
    interleaves those it finds in one trip (Pallas' ``fori_loop`` unrolls
    by 1 or wholly, so the trip is written out).  On the v5e, ms a call of
    before / after / backward at qwen3_next_s16384's shape (my chip runs,
    PR 43): one chunk a trip 1.98 / 2.26 / 6.93, two 1.59 / 1.91 / 6.10,
    four 1.51 / 1.47 / 5.77."""
    per = math.gcd(nb, _GDN_TRIP)

    def trip(t, carry):
        for i in range(per):
            body(t * per + i)
        return carry

    def run():
        jax.lax.fori_loop(0, nb // per, trip, 0)

    _in_a_taken_branch(run)


def _gdn_before_kernel(q_ref, k_ref, rows_ref, qn_ref, a_ref, attn_ref, *,
                       r: int, scale: float):
    """Before the solve: the L2 norms, ``K K^T`` and ``Q K^T`` a key head,
    the decay ratios, ``A`` (float32) and ``attn`` a value head."""
    import jax.experimental.pallas as pl

    nb, c, _ = a_ref.shape
    dt = qn_ref.dtype                   # qn on q's own rows, token-major

    def chunk(n):
        tok = pl.ds(pl.multiple_of(n * c, c), c)
        qn = (_gdn_unit_rows(q_ref[tok, :])[0] * scale).astype(dt)
        kn = _gdn_unit_rows(k_ref[tok, :])[0].astype(dt)
        kk, qk = _dot_nt(kn, kn), _dot_nt(qn, kn)
        rows = rows_ref[n]                                  # [8, C]
        cols = rows.T
        a, attn = [], []
        for v in range(r):
            ratio, strict = _gdn_ratio(cols[:, v:v + 1], rows[v:v + 1])
            beta = cols[:, r + v:r + v + 1]
            a.append(jnp.where(strict, beta * ratio * kk, 0.0))
            attn.append((ratio * qk).astype(dt))
        qn_ref[tok, :] = qn
        a_ref[n] = jnp.concatenate(a, axis=1)
        for v in range(r):
            attn_ref[n, v] = attn[v]

    _gdn_chunks_loop(nb, chunk)


def _gdn_after_kernel(k_ref, v_ref, t_ref, rows_ref, w_ref, u_ref, ko_ref, *,
                      r: int):
    """After the solve: ``U = T (beta V)`` (float32), ``W = T (beta gamma
    K)`` and ``K_out = (gamma_C / gamma) K`` a value head."""
    import jax.experimental.pallas as pl

    nb, c, _ = t_ref.shape
    dt, f32 = w_ref.dtype, jnp.float32
    d = k_ref.shape[-1]

    def chunk(n):
        tok = pl.ds(pl.multiple_of(n * c, c), c)
        kn = _gdn_unit_rows(k_ref[tok, :])[0].astype(dt).astype(f32)
        cols = rows_ref[n].T                                # [C, 8]
        t = t_ref[n].astype(dt)                             # [C, R C]
        for v in range(r):
            gc, beta = cols[:, v:v + 1], cols[:, r + v:r + v + 1]
            gamma = jnp.exp(gc)
            t_v = t[:, v * c:(v + 1) * c]
            bv = (beta * v_ref[tok, v * d:(v + 1) * d].astype(f32)
                  ).astype(dt)
            bgk = ((beta * gamma) * kn).astype(dt)
            u_ref[n, v] = jnp.dot(t_v, bv, preferred_element_type=f32)
            w_ref[n, v] = jnp.dot(t_v, bgk,
                                  preferred_element_type=f32).astype(dt)
            ko_ref[n, v] = (jnp.exp(gc[c - 1:c] - gc) * kn).astype(dt)

    _gdn_chunks_loop(nb, chunk)


def _gdn_small_rows(gc, beta):
    """The per-token scalars as the kernels read them: gc and beta
    [B, N, C, Hk, R] float32 -> [B, N, Hk, 8, C], rows (gc of the R value
    heads, beta of the R value heads, zeros)."""
    b, n, c, hk, r = gc.shape
    rows = jnp.concatenate([gc, beta], -1)                  # [B,N,C,Hk,2R]
    rows = jnp.moveaxis(rows, 2, -1)                        # [B,N,Hk,2R,C]
    return jnp.pad(rows, ((0, 0),) * 3 + ((0, _SUBLANES - 2 * r), (0, 0)))


def _gdn_to_slabs(a):
    """[B, N, Hk, C, R C] (a chunk's R matrices side by side) -> the
    solve's slabs [row, value head, column, (sequence, chunk, key head)]:
    one transpose of the 128 lanes with the matrices."""
    c = a.shape[3]
    slabs = jnp.transpose(a.reshape(-1, c, a.shape[4]), (1, 2, 0))
    return slabs.reshape(c, -1, c, slabs.shape[-1])


def _gdn_from_slabs(slabs, shape):
    """The slabs back side by side, [B, N, Hk, C, R C]."""
    c = slabs.shape[0]
    return jnp.transpose(slabs.reshape(c, -1, slabs.shape[-1]),
                         (2, 0, 1)).reshape(shape)


@functools.partial(jax.jit, static_argnames=("dims", "chunk"))
def gdn_chunk_forward(qkv, gc, beta, dims, chunk: int):
    """The chunk-local passes forward as Mosaic calls: ``before``, PR 34's
    solve on the slabs, ``after``.  qkv [B, N C, 2 Kd + Vd]; gc, beta
    [B, N, C, Hk, R] float32.  Returns (qn [B,N,C,Hk,dk], w, u_own, k_out
    [B,N,Hk,R,C,d], attn [B,N,Hk,R,C,C]) and ``T`` [B,N,Hk,C,R C] in
    float32, the backward's residual.  The caller checks
    :func:`gdn_chunk_tiles` first."""
    hk, hv, dk, dv = dims
    r = hv // hk
    b, l, _ = qkv.shape
    n = l // chunk
    dt, f32 = qkv.dtype, jnp.float32
    nb, _, (q_spec, k_spec, v_spec) = _gdn_blocks(qkv, dims, chunk)
    per_chunk = _gdn_per_chunk(nb)
    grid = (b, n // nb, hk)
    rows = _gdn_small_rows(gc, beta)
    packed = (b, n, hk, chunk, r * chunk)
    qn, a, attn = _gdn_call(
        functools.partial(_gdn_before_kernel, r=r, scale=dk ** -0.5), grid,
        [q_spec, k_spec, per_chunk(_SUBLANES, chunk)],
        [q_spec, per_chunk(chunk, r * chunk), per_chunk(r, chunk, chunk)],
        [((b, l, hk * dk), dt), (packed, f32),
         ((b, n, hk, r, chunk, chunk), dt)],
        (qkv, qkv, rows), "before")
    qn = qn.reshape(b, n, chunk, hk, dk)
    t = _gdn_from_slabs(unit_lower_inverse_slabs(_gdn_to_slabs(a)), packed)
    heads = (b, n, hk, r, chunk)
    w, u_own, k_out = _gdn_call(
        functools.partial(_gdn_after_kernel, r=r), grid,
        [k_spec, v_spec, per_chunk(chunk, r * chunk),
         per_chunk(_SUBLANES, chunk)],
        [per_chunk(r, chunk, dk), per_chunk(r, chunk, dv),
         per_chunk(r, chunk, dk)],
        [(heads + (dk,), dt), (heads + (dv,), f32), (heads + (dk,), dt)],
        (qkv, qkv, t, rows), "after")
    return (qn, w, u_own, k_out, attn), t


def _gdn_bwd_kernel(q_ref, k_ref, v_ref, t_ref, rows_ref, cqn_ref, cw_ref,
                    cu_ref, cko_ref, cattn_ref, dq_ref, dk_ref, dv_ref,
                    drows_ref, *, r: int, scale: float):
    """The rule's backward for a program's chunks (``gated_delta.
    _chunk_bwd_jax`` states it): the norms, the ratios, ``K K^T``,
    ``Q K^T`` and the casts derived again from q, k, v and the scalar rows,
    ``t_ref`` the inverses ([C, R C] float32, transposed here once a chunk
    so that ``T^T x`` and ``T^T ct T^T`` are plain products), the five
    cotangents in; dq,
    dk, dv on the rows' column blocks and the scalars' cotangents as rows
    [8, C] (d gc and d beta of the R value heads) out."""
    import jax.experimental.pallas as pl

    nb, c, _ = t_ref.shape
    dt, f32 = dq_ref.dtype, jnp.float32
    d = k_ref.shape[-1]
    hi = jax.lax.Precision.HIGHEST
    last = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1

    def lanes(x):                       # a sum over the lanes: [C, 1]
        return jnp.sum(x, axis=1, keepdims=True)

    def chunk(n):
        tok = pl.ds(pl.multiple_of(n * c, c), c)
        qy, rq = _gdn_unit_rows(q_ref[tok, :])
        ky, rk = _gdn_unit_rows(k_ref[tok, :])
        qn, kn = (qy * scale).astype(dt), ky.astype(dt)
        knf = kn.astype(f32)
        kk, qk = _dot_nt(kn, kn), _dot_nt(qn, kn)
        rows = rows_ref[n]                                  # [8, C]
        cols = rows.T
        s_all = t_ref[n].T                                  # [R C, C]
        d_kn = jnp.zeros((c, d), f32)
        d_kk = jnp.zeros((c, c), f32)
        d_qk = jnp.zeros((c, c), f32)
        out_cols, out_rows = [], []
        for v in range(r):
            gc, beta = cols[:, v:v + 1], cols[:, r + v:r + v + 1]
            gamma = jnp.exp(gc)
            bg = beta * gamma
            e = jnp.exp(gc[c - 1:c] - gc)
            ratio, strict = _gdn_ratio(gc, rows[v:v + 1])
            vf = v_ref[tok, v * d:(v + 1) * d].astype(f32)
            bv, bgk = (beta * vf).astype(dt), (bg * knf).astype(dt)
            s32 = s_all[v * c:(v + 1) * c]
            sb = s32.astype(dt)
            cu, cw = cu_ref[n, v].astype(dt), cw_ref[n, v]
            d_t = _dot_nt(cu, bv) + _dot_nt(cw, bgk)
            d_bv = jnp.dot(sb, cu, preferred_element_type=f32)
            d_bgk = jnp.dot(sb, cw, preferred_element_type=f32)
            # the inverse: -T^T d_t T^T on the strict lower triangle
            d_a = jnp.dot(jnp.dot(s32, d_t, preferred_element_type=f32,
                                  precision=hi),
                          s32, preferred_element_type=f32, precision=hi)
            dar = jnp.where(strict, -d_a, 0.0) * ratio
            ca = cattn_ref[n, v].astype(f32) * ratio
            d_kk = d_kk + dar * beta
            d_qk = d_qk + ca
            m = dar * beta * kk + ca * qk
            cko = cko_ref[n, v].astype(f32)
            d_e = lanes(cko * knf) * e
            d_bg = lanes(d_bgk * knf)
            d_gc = (lanes(m) + beta * d_bg * gamma - d_e
                    + jnp.where(last, jnp.sum(d_e, axis=0, keepdims=True),
                                0.0))
            d_beta = lanes(dar * kk) + lanes(d_bv * vf) + gamma * d_bg
            d_kn = d_kn + d_bgk * bg + cko * e
            dv_ref[tok, v * d:(v + 1) * d] = (d_bv * beta).astype(dt)
            out_cols.append((d_gc, d_beta))
            out_rows.append(-jnp.sum(m, axis=0, keepdims=True))
        d_kkb, d_qkb = d_kk.astype(dt), d_qk.astype(dt)
        d_kn = (d_kn + jnp.dot(d_kkb, kn, preferred_element_type=f32)
                + _dot_tn(d_kkb, kn) + _dot_tn(d_qkb, qn))
        d_qn = (cqn_ref[tok, :].astype(f32)
                + jnp.dot(d_qkb, kn, preferred_element_type=f32)) * scale
        dq_ref[tok, :] = (rq * (d_qn - qy * lanes(qy * d_qn))).astype(dt)
        dk_ref[tok, :] = (rk * (d_kn - ky * lanes(ky * d_kn))).astype(dt)
        pad = [jnp.zeros((c, 1), f32)] * (_SUBLANES - 2 * r)
        as_cols = jnp.concatenate([g for g, _ in out_cols]
                                  + [b for _, b in out_cols] + pad, axis=1)
        as_rows = jnp.concatenate(
            out_rows + [jnp.zeros((_SUBLANES - r, c), f32)], axis=0)
        drows_ref[n] = as_cols.T + as_rows

    _gdn_chunks_loop(nb, chunk)


@functools.partial(jax.jit, static_argnames=("dims", "chunk"))
def gdn_chunk_backward(qkv, gc, beta, t, cts, dims, chunk: int):
    """The rule's backward as one Mosaic call: the cotangents of (qkv, gc,
    beta) from the residuals of :func:`gdn_chunk_forward` and the
    cotangents ``cts`` of its five outputs."""
    hk, hv, dk, dv = dims
    r = hv // hk
    b, l, _ = qkv.shape
    n = l // chunk
    dt, f32 = qkv.dtype, jnp.float32
    nb, column, (q_spec, k_spec, v_spec) = _gdn_blocks(qkv, dims, chunk)
    per_chunk = _gdn_per_chunk(nb)
    ct_qn, ct_w, ct_u, ct_kout, ct_attn = cts
    rows_spec = per_chunk(_SUBLANES, chunk)
    d_q, d_k, d_v, d_rows = _gdn_call(
        functools.partial(_gdn_bwd_kernel, r=r, scale=dk ** -0.5),
        (b, n // nb, hk),
        [q_spec, k_spec, v_spec, per_chunk(chunk, r * chunk), rows_spec,
         q_spec, per_chunk(r, chunk, dk),
         per_chunk(r, chunk, dv), per_chunk(r, chunk, dk),
         per_chunk(r, chunk, chunk)],
        # dq, dk and dv each on rows of their own: column block h
        [q_spec, q_spec, column(r * dv), rows_spec],
        [((b, l, hk * dk), dt), ((b, l, hk * dk), dt), ((b, l, hv * dv), dt),
         ((b, n, hk, _SUBLANES, chunk), f32)],
        (qkv, qkv, qkv, t, _gdn_small_rows(gc, beta),
         ct_qn.reshape(b, l, hk * dk), ct_w, ct_u, ct_kout, ct_attn),
        "bwd")
    d_small = jnp.moveaxis(d_rows[:, :, :, :2 * r], -1, 2)  # [B,N,C,Hk,2R]
    return (jnp.concatenate([d_q, d_k, d_v], -1), d_small[..., :r],
            d_small[..., r:])


# The Mamba-2 scan's chunk passes (ops/ssd.py states the mathematics and
# the rule; these are its Mosaic schedule).  A program is (sequence, chunk,
# block of 8 heads): x of the heads, B and C are column blocks of the
# convolution's own [B, L, I + 2 S] rows, the chunk's [C, C] pairs of a head
# are made, used and dropped in VMEM, and the per-token scalars (the running
# log-decay and delta of each head) come as rows [8, C] that a program turns
# into columns itself.  Products are made a unit of lanes at a time: one
# head of whole lane tiles, or the 128 // P heads that fill one tile, each
# product then on all of the tile's lanes and the head's own kept
# (``_keep_head``): the MXU is 128 wide whatever the head.

_SSD_VMEM = 48 << 20
_SSD_STATE, _SSD_OUT, _SSD_STATE_BWD, _SSD_OUT_BWD = range(4)


def ssd_chunk_tiles(rows: int, dims, chunk: int) -> bool:
    """Whether the chunk kernels take rows of ``rows`` tokens with ``dims``
    = (H, P, G, S): whole chunks of whole lane tiles, one group (a
    chunk's ``C B^T`` serves every head of a program), heads in blocks of
    8 (the scalars' sublanes) whose lanes are whole units of one head or
    of the heads that fill a tile, a state of whole lane tiles whose
    columns start on one of its own blocks."""
    h, p, g, s = dims
    unit = max(p, 128)
    return (g == 1 and chunk % 128 == 0 and rows % chunk == 0
            and h % _SUBLANES == 0 and unit % p == 0 and unit % 128 == 0
            and (_SUBLANES * p) % unit == 0 and s % 128 == 0
            and (h * p) % s == 0)


def _ssd_specs(dims, chunk: int):
    """The block specs of a program of the grid (B, N, H / 8): ``x``, its
    heads' lanes of token-major rows; ``b`` and ``c`` on the rows
    [B, L, I + 2 S]; ``rows``, the scalars [B, N, H / 8, 2, 8, C];
    ``states``, its heads' of [N, B, H P, S]; ``lanes``, one row of its
    lanes [B, N, 1, H P]; ``skip``, the same of [1, H P]; ``b_sum`` and
    ``bc_sum``, the chunk's rows of [B, L, S] and [B, L, 2 S] (every block
    of heads the same block)."""
    import jax.experimental.pallas as pl

    h, p, _, s = dims
    lanes, first = _SUBLANES * p, h * p // s
    return dict(
        x=pl.BlockSpec((None, chunk, lanes), lambda b, j, k: (b, j, k)),
        b=pl.BlockSpec((None, chunk, s), lambda b, j, k: (b, j, first)),
        c=pl.BlockSpec((None, chunk, s), lambda b, j, k: (b, j, first + 1)),
        rows=pl.BlockSpec((None, None, None, 2, _SUBLANES, chunk),
                          lambda b, j, k: (b, j, k, 0, 0, 0)),
        states=pl.BlockSpec((None, None, lanes, s),
                            lambda b, j, k: (j, b, k, 0)),
        lanes=pl.BlockSpec((None, None, 1, lanes),
                           lambda b, j, k: (b, j, 0, k)),
        skip=pl.BlockSpec((1, lanes), lambda b, j, k: (0, k)),
        b_sum=pl.BlockSpec((None, chunk, s), lambda b, j, k: (b, j, 0)),
        bc_sum=pl.BlockSpec((None, chunk, 2 * s), lambda b, j, k: (b, j, 0)))


def _ssd_call(kernel, grid, in_specs, out_specs, out_shape, operands, part):
    """One of the four calls (``part``), on the grid (B, N, H / 8).  The
    blocks of heads are taken in turn: B's and C's cotangents are summed
    over them in a block that stays in VMEM."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    vma = _vma_kw(*operands)
    out_shape = [jax.ShapeDtypeStruct(s, d, **vma) for s, d in out_shape]
    with kernel_scope(("ssd_chunk_state", "ssd_chunk_out",
                       "ssd_chunk_state_bwd", "ssd_chunk_out_bwd")[part]):
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_SSD_VMEM),
            interpret=_use_interpret(),
        )(*operands)


def _ssd_small_rows(l, delta, chunk: int):
    """The per-token scalars as the kernels read them: the running
    log-decay and delta [B, L, H] float32 -> [B, N, H / 8, 2, 8, C]."""
    b, length, h = l.shape
    rows = jnp.stack([l, delta], 2).reshape(
        b, length // chunk, chunk, 2, h // _SUBLANES, _SUBLANES)
    return jnp.transpose(rows, (0, 1, 4, 3, 5, 2))


def _ssd_from_small_rows(rows):
    """The cotangents' rows [B, N, H / 8, 2, 8, C] back token-major: two
    arrays [B, L, H]."""
    b, n, _, _, _, c = rows.shape
    cols = jnp.transpose(rows, (3, 0, 1, 5, 2, 4)).reshape(2, b, n * c, -1)
    return cols[0], cols[1]


def _ssd_spread(cols, first: int, shape, p: int):
    """Per-token columns [C, 8] on a unit's lanes ``shape`` = [C, U]:
    column ``first + i`` on the lanes of the unit's head i."""
    out = cols[:, first:first + 1]
    if shape[1] == p:
        return out                      # one head: the product broadcasts
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    for i in range(1, shape[1] // p):
        out = jnp.where(lane >= i * p, cols[:, first + i:first + i + 1], out)
    return out


def _ssd_units(lanes: int, p: int):
    """The units a program's ``lanes`` are worked in: (a unit's lanes, its
    first head) each, and the heads a unit."""
    unit = max(p, 128)
    return [(slice(u, u + unit), u // p)
            for u in range(0, lanes, unit)], unit // p


def _ssd_head_sums(x, p: int):
    """The sums over each head's lanes of a unit x [C, U]: U // P columns
    [C, 1]."""
    heads = x.shape[1] // p
    return [jnp.sum(_keep_head(x, i, heads), axis=1, keepdims=True)
            for i in range(heads)]


def _ssd_scalars(rows_ref):
    """The running log-decay and delta of the program's heads as rows
    [8, C] and as columns [C, 8]."""
    l_rows, d_rows = rows_ref[0], rows_ref[1]
    return l_rows, l_rows.T, d_rows.T


def _ssd_to_end(l_cols, d_cols):
    """``exp(l_C - l_j)`` and delta_j times it, columns [C, 8]."""
    c = l_cols.shape[0]
    w = jnp.exp(l_cols[c - 1:c] - l_cols)
    return w, d_cols * w


def _ssd_kernel(body):
    """A kernel of the four from its body: the program's place among the
    blocks of heads is read here, outside the branch the interpreter
    needs around the body (:func:`_in_a_taken_branch`)."""
    @functools.wraps(body)
    def kernel(*refs, p: int):
        import jax.experimental.pallas as pl

        block = pl.program_id(2)
        _in_a_taken_branch(lambda: body(*refs, p=p, block=block))
    return kernel


@_ssd_kernel
def _ssd_state_kernel(x_ref, b_ref, rows_ref, s_ref, *, p: int, block):
    """Each head's own state of the chunk: ``sum_j exp(l_C - l_j) delta_j
    x_j B_j^T`` [8 P, S] in float32."""
    del block
    dt, f32 = x_ref.dtype, jnp.float32
    _, l_cols, d_cols = _ssd_scalars(rows_ref)
    _, to_end = _ssd_to_end(l_cols, d_cols)
    bm = b_ref[...]
    for at, first in _ssd_units(x_ref.shape[1], p)[0]:
        x = x_ref[:, at].astype(f32)
        xw = (x * _ssd_spread(to_end, first, x.shape, p)).astype(dt)
        s_ref[at, :] = _dot_tn(xw, bm)


def _ssd_sum_over_blocks(ref, value, block):
    """``value`` written by the first block of heads and added by the
    others, in the block of ``ref`` that all of them share."""
    import jax.experimental.pallas as pl

    @pl.when(block == 0)
    def _():
        ref[...] = value

    @pl.when(block > 0)
    def _():
        ref[...] += value


@_ssd_kernel
def _ssd_state_bwd_kernel(x_ref, b_ref, rows_ref, ds_ref, dx_ref, db_ref,
                          drows_ref, *, p: int, block):
    """The backward of :func:`_ssd_state_kernel` (``ssd._state_bwd_jax``
    states it): x's cotangent on its rows, B's summed over the blocks of
    heads in float32, the scalars' as rows."""
    c = x_ref.shape[0]
    dt, f32 = x_ref.dtype, jnp.float32
    _, l_cols, d_cols = _ssd_scalars(rows_ref)
    w, to_end = _ssd_to_end(l_cols, d_cols)
    bm = b_ref[...]
    dsb = ds_ref[...].astype(dt)
    d_xw = _dot_nt(bm, dsb)                              # [C, 8 P]
    d_b = jnp.zeros(bm.shape, f32)
    d_te = []
    for at, first in _ssd_units(x_ref.shape[1], p)[0]:
        x = x_ref[:, at].astype(f32)
        spread = _ssd_spread(to_end, first, x.shape, p)
        d_b = d_b + jnp.dot((x * spread).astype(dt), dsb[at],
                            preferred_element_type=f32)
        dx_ref[:, at] = (d_xw[:, at] * spread).astype(dt)
        d_te += _ssd_head_sums(d_xw[:, at] * x, p)
    d_te = jnp.concatenate(d_te, axis=1)                 # [C, 8]
    g = d_te * to_end
    last = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0) == c - 1
    d_l = jnp.where(last, jnp.sum(g, axis=0, keepdims=True), 0.0) - g
    drows_ref[0] = d_l.T
    drows_ref[1] = (d_te * w).T
    _ssd_sum_over_blocks(db_ref, d_b, block)


def _ssd_scores(c, b):
    """``C B^T`` of the chunk [C, C] in float32, once for the program's
    heads, and the mask of the pairs j <= i."""
    scores = _dot_nt(c, b)
    i = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    j = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    return scores, j <= i


def _ssd_pairs(scores, lower, l_rows, l_cols, head: int, dt):
    """A head's masked pairs ``(C_i . B_j) exp(l_i - l_j)`` [C, C] in the
    compute dtype, and the ratios in float32: exactly 0 above the
    diagonal."""
    ratio = jnp.exp(jnp.where(
        lower, l_cols[:, head:head + 1] - l_rows[head:head + 1], -jnp.inf))
    return (scores * ratio).astype(dt), ratio


@_ssd_kernel
def _ssd_out_kernel(x_ref, b_ref, c_ref, rows_ref, s_ref, skip_ref, y_ref,
                    *, p: int, block):
    """y of the program's heads for the chunk's tokens: the masked pairs'
    product with ``delta x``, what the entering states add, and ``D x``,
    summed in VMEM and written once."""
    del block
    units, heads = _ssd_units(x_ref.shape[1], p)
    dt, f32 = x_ref.dtype, jnp.float32
    l_rows, l_cols, d_cols = _ssd_scalars(rows_ref)
    e_cols = jnp.exp(l_cols)
    cm = c_ref[...]
    scores, lower = _ssd_scores(cm, b_ref[...])
    y_in = _dot_nt(cm, s_ref[...])                       # [C, 8 P]
    for at, first in units:
        x = x_ref[:, at].astype(f32)
        xd = (x * _ssd_spread(d_cols, first, x.shape, p)).astype(dt)
        y = None
        for i in range(heads):
            m, _ = _ssd_pairs(scores, lower, l_rows, l_cols, first + i, dt)
            own = jnp.dot(m, xd, preferred_element_type=f32)
            y = own if y is None else jnp.where(
                _head_lanes(own, i, heads), own, y)
        y_ref[:, at] = (y + _ssd_spread(e_cols, first, x.shape, p)
                        * y_in[:, at] + skip_ref[:, at] * x)


@_ssd_kernel
def _ssd_out_bwd_kernel(x_ref, b_ref, c_ref, rows_ref, s_ref, skip_ref,
                        dy_ref, dx_ref, dbc_ref, ds_ref, drows_ref,
                        dskip_ref, *, p: int, block):
    """The backward of :func:`_ssd_out_kernel` (``ssd._out_bwd_jax``
    states it), the pairs made again in VMEM: x's cotangent on its rows,
    B's and C's [C, 2 S] summed over the blocks of heads in float32, the
    entering states', the scalars' as rows (a token's share of l's
    through its row of the pairs is ``dy . y_own``, through its column
    ``(delta x) . d(delta x)``: no sum over a [C, C] array), and ``D``'s a
    lane, summed over the chunk's tokens."""
    units, heads = _ssd_units(x_ref.shape[1], p)
    dt, f32 = x_ref.dtype, jnp.float32
    l_rows, l_cols, d_cols = _ssd_scalars(rows_ref)
    e_cols = jnp.exp(l_cols)
    bm, cm = b_ref[...], c_ref[...]
    scores, lower = _ssd_scores(cm, bm)
    y_in = _dot_nt(cm, s_ref[...])
    d_scores = jnp.zeros(scores.shape, f32)
    d_c = jnp.zeros(cm.shape, f32)
    d_l, d_delta = [], []
    for at, first in units:
        x, dy = x_ref[:, at].astype(f32), dy_ref[:, at]
        dyb = dy.astype(dt)
        delta = _ssd_spread(d_cols, first, x.shape, p)
        e = _ssd_spread(e_cols, first, x.shape, p)
        xd = (x * delta).astype(dt)
        y = d_xd = None
        for i in range(heads):
            m, ratio = _ssd_pairs(scores, lower, l_rows, l_cols, first + i,
                                  dt)
            own = jnp.dot(m, xd, preferred_element_type=f32)
            d_own = _dot_tn(m, dyb)
            if i:
                mine = _head_lanes(own, i, heads)
                y, d_xd = jnp.where(mine, own, y), jnp.where(mine, d_own,
                                                             d_xd)
            else:
                y, d_xd = own, d_own
            d_scores = d_scores + _dot_nt(_keep_head(dyb, i, heads),
                                          xd) * ratio
        # the pairs' share of l's cotangent, rows less columns, from the
        # products' own operands: the same terms summed two ways, so that
        # a chunk's shares cancel as the pairs' ratios say they must
        d_l += _ssd_head_sums(dyb.astype(f32) * y + dy * e * y_in[:, at]
                              - xd.astype(f32) * d_xd, p)
        d_delta += _ssd_head_sums(x * d_xd, p)
        dx_ref[:, at] = (d_xd * delta + skip_ref[:, at] * dy).astype(dt)
        dskip_ref[:, at] = jnp.sum(dy * x, axis=0, keepdims=True)
        eyb = (dy * e).astype(dt)
        ds_ref[at, :] = _dot_tn(eyb, cm).astype(dt)
        d_c = d_c + jnp.dot(eyb, s_ref[at, :], preferred_element_type=f32)
    drows_ref[0] = jnp.concatenate(d_l, axis=1).T        # [C, 8] -> rows
    drows_ref[1] = jnp.concatenate(d_delta, axis=1).T
    d_sb = d_scores.astype(dt)
    d_c = d_c + jnp.dot(d_sb, bm, preferred_element_type=f32)
    _ssd_sum_over_blocks(
        dbc_ref, jnp.concatenate([_dot_tn(d_sb, cm), d_c], axis=1), block)


def _ssd_grid(xbc, dims, chunk: int):
    """The grid (B, N, H / 8) of the rows ``xbc``."""
    return (xbc.shape[0], xbc.shape[1] // chunk, dims[0] // _SUBLANES)


@functools.partial(jax.jit, static_argnames=("dims", "chunk"))
def ssd_chunk_state(xbc, delta, l, dims, chunk: int):
    """Each chunk's own contribution to the state, ``ssd._state_fwd_jax``
    as one Mosaic call: xbc [B, L, I + 2 S] (columns [x | B | C]), delta
    and the running log-decay ``l`` [B, L, H] float32 -> [N, B, H P, S]
    float32.  The caller checks :func:`ssd_chunk_tiles` first."""
    h, p, _, s = dims
    rows = _ssd_small_rows(l, delta, chunk)
    grid = _ssd_grid(xbc, dims, chunk)
    spec = _ssd_specs(dims, chunk)
    own, = _ssd_call(
        functools.partial(_ssd_state_kernel, p=p), grid,
        [spec["x"], spec["b"], spec["rows"]], [spec["states"]],
        [((grid[1], grid[0], h * p, s), jnp.float32)], (xbc, xbc, rows),
        _SSD_STATE)
    return own


@functools.partial(jax.jit, static_argnames=("dims", "chunk"))
def ssd_chunk_state_bwd(xbc, delta, l, d_own, dims, chunk: int):
    """The backward of :func:`ssd_chunk_state` as one Mosaic call: the
    cotangents of the rows ``xbc`` (C's columns zeros), of delta and of
    the running log-decay [B, L, H]."""
    h, p, _, s = dims
    rows = _ssd_small_rows(l, delta, chunk)
    b, length, _ = xbc.shape
    grid = _ssd_grid(xbc, dims, chunk)
    spec = _ssd_specs(dims, chunk)
    d_x, d_b, d_rows = _ssd_call(
        functools.partial(_ssd_state_bwd_kernel, p=p), grid,
        [spec["x"], spec["b"], spec["rows"], spec["states"]],
        [spec["x"], spec["b_sum"], spec["rows"]],
        [((b, length, h * p), xbc.dtype), ((b, length, s), jnp.float32),
         (rows.shape, jnp.float32)],
        (xbc, xbc, rows, d_own), _SSD_STATE_BWD)
    d_l, d_delta = _ssd_from_small_rows(d_rows)
    d_xbc = jnp.concatenate(
        [d_x, d_b.astype(xbc.dtype), jnp.zeros((b, length, s), xbc.dtype)],
        -1)
    return d_xbc, d_delta, d_l


@functools.partial(jax.jit, static_argnames=("dims", "chunk"))
def ssd_chunk_out(xbc, delta, l, s_in, skip, dims, chunk: int):
    """y [B, L, H P] float32 from the rows, the scalars, the states
    entering the chunks ``s_in`` [N, B, H P, S] (the compute dtype) and
    ``D`` a lane ``skip`` [1, H P] float32: ``ssd._out_fwd_jax`` as one
    Mosaic call."""
    h, p, _, _ = dims
    rows = _ssd_small_rows(l, delta, chunk)
    grid = _ssd_grid(xbc, dims, chunk)
    spec = _ssd_specs(dims, chunk)
    y, = _ssd_call(
        functools.partial(_ssd_out_kernel, p=p), grid,
        [spec["x"], spec["b"], spec["c"], spec["rows"], spec["states"],
         spec["skip"]], [spec["x"]],
        [(xbc.shape[:2] + (h * p,), jnp.float32)],
        (xbc, xbc, xbc, rows, s_in, skip), _SSD_OUT)
    return y


@functools.partial(jax.jit, static_argnames=("dims", "chunk"))
def ssd_chunk_out_bwd(xbc, delta, l, s_in, skip, d_y, dims, chunk: int):
    """The backward of :func:`ssd_chunk_out` as one Mosaic call: the
    cotangents of the rows ``xbc``, of delta and the running log-decay
    [B, L, H], of ``s_in`` and of ``skip``."""
    h, p, _, s = dims
    rows = _ssd_small_rows(l, delta, chunk)
    b, length, _ = xbc.shape
    grid = _ssd_grid(xbc, dims, chunk)
    spec = _ssd_specs(dims, chunk)
    d_x, d_bc, d_s, d_rows, d_skip = _ssd_call(
        functools.partial(_ssd_out_bwd_kernel, p=p), grid,
        [spec["x"], spec["b"], spec["c"], spec["rows"], spec["states"],
         spec["skip"], spec["x"]],
        [spec["x"], spec["bc_sum"], spec["states"], spec["rows"],
         spec["lanes"]],
        [((b, length, h * p), xbc.dtype), ((b, length, 2 * s), jnp.float32),
         (s_in.shape, s_in.dtype), (rows.shape, jnp.float32),
         ((b, grid[1], 1, h * p), jnp.float32)],
        (xbc, xbc, xbc, rows, s_in, skip, d_y), _SSD_OUT_BWD)
    d_l, d_delta = _ssd_from_small_rows(d_rows)
    return (jnp.concatenate([d_x, d_bc.astype(xbc.dtype)], -1), d_delta, d_l,
            d_s, d_skip.sum((0, 1)))


_ROPE_BLOCK_BYTES = 1 << 21     # a program's block as a float32 slab


def _rope_block(x, head_dim: int) -> Optional[Tuple[int, int, int]]:
    """(batch rows, sequence rows, lanes) of a block of :func:`_rope_call`
    on rows ``x`` [B, L, H*D], or None where the kernel has none and XLA
    computes :func:`_rope_heads_xla`: off the TPU (there the kernel would
    run in the Pallas interpreter: every CPU test of the model would pay
    it), for a shape whose ``_heads_per_program`` block is not whole
    128-lane tiles, and for a sequence that has no block of whole sublane
    tiles (a decode step's L = 1, an odd L).  Read from the platform and
    the shape.  A block is as many rows as make a 2 MiB float32 slab (the
    power of two that divides L; 0.107 ms a call at [8, 4096, 1024] against
    0.245 at 256 rows, PERF.md PR 36): of one sequence, or, where a
    sequence is shorter, of several."""
    b, l, width = x.shape
    per = _heads_per_program(width // head_dim, width // head_dim, head_dim)
    if _use_interpret() or per is None or (per * head_dim) % 128:
        return None
    lanes = per * head_dim
    most = _ROPE_BLOCK_BYTES // (4 * lanes)
    rows = math.gcd(l, most)            # most is a power of two
    if rows % _sublane_tile(x.dtype):
        return None
    return (math.gcd(b, most // rows) if rows == l else 1), rows, lanes


def _rope_heads_xla(x, cos, sin, half: int):
    """:func:`rope` in plain ``jnp`` on heads x [B, L, H, D], each head
    sliced in its halves: the form XLA fuses onto a q / k norm (two rolls
    and a select it does not: the described-v5e compile of
    ``qwen3_next_s16384`` reads 25 relayouts under ``hvdt.attention`` and
    1.1% more memory with them, 16 and the parent's bytes with this; a
    Mosaic call after the norm 20 and the same 1.1%)."""
    c, s = cos[:, :, None, :half], sin[:, :, None, half:2 * half]
    x1, x2 = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate(
        [x1 * c - x2 * s, x1 * s + x2 * c, x[..., 2 * half:]],
        -1).astype(x.dtype)


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref, *, head_dim: int, half: int,
                 conj: bool):
    """One block of :func:`rope`, [batch rows x sequence rows, W] with W
    whole heads: two lane rolls of the block and a select between them by
    the lane's place in its head; a lane a roll brings in around the
    block's end meets a 0 of ``sin``."""
    from jax.experimental.pallas import tpu as pltpu

    w = x_ref.shape[-1]
    x = x_ref[...].reshape(-1, w).astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    if w != head_dim:                   # W = 128 lanes of heads of 2^n
        lane = lane & (head_dim - 1)
    pair = jnp.where(lane < half, pltpu.roll(x, w - half, 1),
                     pltpu.roll(x, half, 1)) * sin_ref[...].reshape(-1, w)
    o_ref[...] = (x * cos_ref[...].reshape(-1, w)
                  + (-pair if conj else pair)).astype(o_ref.dtype).reshape(
                      o_ref.shape)


def _rope_call(x, cos, sin, *, half: int, conj: bool, block):
    """:func:`rope` as one Mosaic call on x [B, L, H*D]: grid (batch
    block, sequence block, head block), the activations' blocks ``block``
    = (batch rows, sequence rows, lanes), the lanes as the flash calls
    take them, the tables' the same at (batch block, sequence block):
    their index does not change along the last grid axis, so a table block
    is fetched once for all the heads."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ..parallel.sharding import pcast_to_union

    b, l, width = x.shape
    batch, rows, lanes = block
    head_dim = cos.shape[-1]
    # One head's tables to a block's lanes; under shard_map the tables
    # come from positions no axis varies over.
    cos, sin = (pcast_to_union(
        jnp.concatenate([t] * (lanes // head_dim), -1), x)
        for t in (cos, sin))
    xspec = pl.BlockSpec(block, lambda bb, ll, hh: (bb, ll, hh))
    tspec = pl.BlockSpec(block, lambda bb, ll, hh: (bb, ll, 0))
    with kernel_scope("rope"):
        return pl.pallas_call(
            functools.partial(_rope_kernel, head_dim=head_dim, half=half,
                              conj=conj),
            grid=(b // batch, l // rows, width // lanes),
            in_specs=[xspec, tspec, tspec], out_specs=xspec,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           **_vma_kw(x, cos, sin)),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            cost_estimate=pl.CostEstimate(
                flops=4 * x.size, transcendentals=0,
                bytes_accessed=2 * x.size * x.dtype.itemsize
                + 8 * b * l * lanes),
            interpret=_use_interpret(),
        )(x, cos, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rope_rows(x, cos, sin, half: int, block):
    return _rope_call(x, cos, sin, half=half, conj=False, block=block)


def _rope_rows_fwd(x, cos, sin, half, block):
    return _rope_rows(x, cos, sin, half, block), (cos, sin)


def _rope_rows_bwd(half, block, tables, g):
    # The transpose of a rotation is the rotation by the negated angle.
    return (_rope_call(g, *tables, half=half, conj=True, block=block),
            None, None)


_rope_rows.defvjp(_rope_rows_fwd, _rope_rows_bwd)


def rope(x, cos, sin, half: int):
    """The rotary embedding.  ``cos``, ``sin``: one head's tables [B, L, D]
    float32, ``sin`` signed (minus on the lower lane of a pair, 0 on a
    lane that does not rotate, where ``cos`` is 1); ``half``: the distance
    between the lanes of a pair.  Lane i of a head takes ``x[i] cos[i] +
    x[pair of i] sin[i]``, the pair being lane i + half for the lower
    lanes and i - half for the upper.  Products in float32, one rounding
    to ``x``'s dtype.

    x is either the rows a projection wrote, [B, L, H*D], whole heads
    side by side: on a TPU one Mosaic call on the rows' own lanes where
    :func:`_rope_block` has a block (two lane rolls of a block and a
    select between them, in place of slices of each head), so that
    nothing between the projections and the flash kernels asks XLA for
    another layout than theirs: sliced in halves of 32 or 64 lanes, XLA
    lays the whole attention block sequence-minor and copies q, k, dq and
    dk to and from the kernels.  Or heads [B, L, H, D], what a q / k norm
    or the split of an elementwise gate leaves, and rows with no block:
    the same products from slices of each head in plain ``jnp``, which XLA
    fuses onto its neighbours and JAX transposes itself."""
    block = None if x.ndim == 4 else _rope_block(x, cos.shape[-1])
    if block is not None:
        return _rope_rows(x, cos, sin, half, block)
    heads = x.reshape(x.shape[:2] + (-1, cos.shape[-1]))
    return _rope_heads_xla(heads, cos, sin, half).reshape(x.shape)


# A third kernel that is not flash attention (the module's first lines name
# two): ``moe_sum_rows`` returns the experts' rows to their tokens
# (``parallel/moe.py``), the runs of the sorted buffer that a tile of tokens
# needs DMA'd into VMEM and summed there by a 0/1 product, where XLA writes
# the gathered copy and reads it back.

_SUM_ROWS_TILE = 128            # tokens a 0/1 product returns at once
_SUM_ROWS_PROGRAM = 1024        # tokens a program: 8 tiles, two in flight
_SUM_ROWS_GROUP = 8             # rows a DMA: a tile of a tiled dimension
_SUM_ROWS_WORDS = 128           # a tile's share of a rank-1 SMEM block
_SUM_ROWS_SLAB = 512            # lanes of the product's result at once
_SUM_ROWS_SPARE = 16 << 20      # VMEM for the product's operands and result
_SUM_ROWS_VMEM = 100 << 20


def _moe_sum_rows_blocks(t: int, k: int, d: int, segments: int, dtype
                         ) -> Optional[Tuple[int, int]]:
    """(staged groups a tile, bytes of VMEM) of :func:`moe_sum_rows` on
    ``t`` tokens of ``k`` picks, rows of ``d`` in ``segments`` runs of the
    sorted buffer (the held experts and, last, the picks that are not
    held), or None where the kernel has no blocks and the caller keeps
    XLA's gather and sum: the tokens have to be whole programs of 1,024 (a
    rank-1 SMEM block is whole tiles of 1,024 words), a row whole lanes,
    and the ends of the runs, which the kernel fetches in whole groups of
    8 rows, at most as many rows again as the picks (so many experts that
    a tile of tokens sends most of them less than a group each are better
    served by a gather).  Read from the shapes alone."""
    g, tile = _SUM_ROWS_GROUP, _SUM_ROWS_TILE
    ends = (segments - 1) * (2 * g - 2)
    if t % _SUM_ROWS_PROGRAM or d % 128 or k < 1 or ends > tile * k:
        return None
    staged = -(-(tile * k + ends) // 128) * 128
    row = d * jnp.dtype(dtype).itemsize
    vmem = ((2 * staged + 8 * g + 2 * _SUM_ROWS_PROGRAM) * row
            + _SUM_ROWS_SPARE)
    return (staged // g, vmem) if vmem <= _SUM_ROWS_VMEM else None


def _moe_sum_rows_plan(inverse, held, segment, segments: int, groups: int):
    """What a tile of 128 tokens fetches and where its picks' rows then
    lie, from the picks' places in the sorted buffer: (``place`` [k up to
    8s, T], ``fetch`` [tiles * stride], ``counts`` [tiles * 128]).

    The buffer is sorted by segment and, inside one, by pick, so the picks
    a tile sends to a segment are ONE run of consecutive rows: run s of
    tile i starts where segment s starts plus what the tiles before sent
    there.  The tile stages each held segment's run in whole groups of 8
    rows, one behind the other: ``fetch`` lists the buffer's group for
    each staged group, ``place`` is a held pick's row among the staged
    ones (-1 for a pick that is not held).  ``counts`` holds, a tile, the
    staged groups, the first group and the groups of the run that is not
    held (moved too, to where nothing reads it), and which staged group
    holds the end of the rows that landed with how many of its rows to
    keep (-1: none does): behind them lies what the products left there,
    which may be anything."""
    t, k = held.shape
    g, r = _SUM_ROWS_GROUP, _SUM_ROWS_TILE * k
    n = t // _SUM_ROWS_TILE
    ids = jnp.arange(segments, dtype=jnp.int32)
    mine = segment.reshape(n, r, 1) == ids                      # [n, r, S]
    count = mine.sum(1, dtype=jnp.int32)                        # [n, S]
    whole = count.sum(0)
    start = (jnp.cumsum(whole) - whole)[None] + jnp.cumsum(count, 0) - count
    first = start - start % g                   # the run's first group's row
    fetched = jnp.where(count > 0, (start - first + count + g - 1) // g, 0)
    staged = fetched.at[:, -1].set(0)
    end = jnp.cumsum(staged, 1)
    at = end - staged                           # the run's first staged group
    of_pick = jnp.where(mine, (at * g - first)[:, None, :], 0).sum(-1)
    place = jnp.where(held.reshape(n, r), of_pick + inverse.reshape(n, r), -1)
    u = jnp.arange(groups, dtype=jnp.int32)
    run = (u[None, :, None] >= end[:, None, :]).sum(-1)         # [n, groups]
    fetch = jnp.where(run[..., None] == ids, (first // g - at)[:, None, :],
                      0).sum(-1) + u
    landed = whole[:-1].sum()
    keep = landed % g
    last = (fetch == landed // g) & (u < end[:, -1:]) & (keep > 0)
    counts = jnp.stack(
        [end[:, -1], first[:, -1] // g, fetched[:, -1],
         jnp.where(last.any(1), jnp.argmax(last, 1), -1),
         jnp.broadcast_to(keep, (n,))], 1).astype(jnp.int32)
    words = _SUM_ROWS_WORDS
    # Tokens on the lanes: [T, k] would be padded to 128 lanes in HBM.
    place = jnp.pad(place.reshape(t, k).T.astype(jnp.int32),
                    ((0, -k % 8), (0, 0)), constant_values=-1)
    return (place,
            jnp.pad(fetch.astype(jnp.int32),
                    ((0, 0), (0, -groups % words))).reshape(-1),
            jnp.pad(counts, ((0, 0), (0, words - 5))).reshape(-1))


def _moe_sum_rows_kernel(fetch_ref, counts_ref, place_ref, rows_hbm, out_ref,
                         stage, aside, sem, *, k: int, groups: int,
                         precision):
    """One program of :func:`moe_sum_rows`: 8 tiles of 128 tokens, the
    next tile's groups on their way while this one's rows are summed.
    ``fetch_ref`` / ``counts_ref`` (SMEM) and ``place_ref`` [k, tokens] are
    :func:`_moe_sum_rows_plan`'s; ``rows_hbm`` [rows, D] stays in HBM;
    ``stage`` [2, staged rows, D] takes a tile's held runs, ``aside`` the
    groups of the run that is not held: EVERY pick's row is moved, so the
    time is the buffer's and not the routing's, and nothing reads those.
    A token's row is then a 0/1 product: row t of ``sel`` [128, staged
    rows] is 1 at the token's held picks' places, so ``sel @ stage`` is
    the float32 sum of exactly those rows (a product by 1 is exact), with
    one rounding.  A staged row no pick of the tile points at meets only
    zeros; it has to be finite, and is: another tile's row, or zero (the
    stage starts as zeros, and the rows behind the last that landed are
    zeroed where a group brings them)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g, tile, words = _SUM_ROWS_GROUP, _SUM_ROWS_TILE, _SUM_ROWS_WORDS
    d = out_ref.shape[1]
    staged = groups * g
    stride = -(-groups // words) * words

    def group(at, dst, slot):
        return pltpu.make_async_copy(
            rows_hbm.at[pl.ds(pl.multiple_of(at * g, g), g), :], dst,
            sem.at[slot])

    def start(c, slot):
        def held(u, carry):
            group(fetch_ref[c * stride + u],
                  stage.at[slot, pl.ds(pl.multiple_of(u * g, g), g), :],
                  slot).start()
            return carry

        def rest(u, carry):
            group(counts_ref[c * words + 1] + u,
                  aside.at[u % aside.shape[0]], slot).start()
            return carry

        jax.lax.fori_loop(0, counts_ref[c * words], held, 0)
        jax.lax.fori_loop(0, counts_ref[c * words + 2], rest, 0)

    def wait(c, slot):
        def one(u, carry):
            group(0, aside.at[0], slot).wait()
            return carry

        jax.lax.fori_loop(
            0, counts_ref[c * words] + counts_ref[c * words + 2], one, 0)

    def total(c, slot):
        last, keep = counts_ref[c * words + 3], counts_ref[c * words + 4]

        @pl.when(last >= 0)
        def _():
            base = pl.multiple_of(last * g // 16 * 16, 16)
            rows = stage[slot, pl.ds(base, 16), :]
            at = base + jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
            stage[slot, pl.ds(base, 16), :] = jnp.where(
                (at >= last * g + keep) & (at < (last + 1) * g),
                jnp.zeros_like(rows), rows)

        row0 = pl.multiple_of(c * tile, tile)
        place = place_ref[:, pl.ds(row0, tile)].T               # [tile, k]
        at = jax.lax.broadcasted_iota(jnp.int32, (tile, staged), 1)
        sel = at == place[:, 0:1]
        for j in range(1, k):
            sel = sel | (at == place[:, j:j + 1])
        sel = sel.astype(stage.dtype)
        for d0 in range(0, d, min(d, _SUM_ROWS_SLAB)):
            lanes = slice(d0, d0 + min(d, _SUM_ROWS_SLAB))
            out_ref[pl.ds(row0, tile), lanes] = jnp.dot(
                sel, stage[slot, :, lanes],
                preferred_element_type=jnp.float32,
                precision=precision).astype(out_ref.dtype)

    stage[...] = jnp.zeros_like(stage)
    start(0, 0)

    def body(c, carry):
        @pl.when(c + 1 < out_ref.shape[0] // tile)
        def _():
            start(c + 1, 1 - c % 2)

        wait(c, c % 2)
        total(c, c % 2)
        return carry

    jax.lax.fori_loop(0, out_ref.shape[0] // tile, body, 0)


@functools.partial(jax.jit, static_argnames=("segments",))
def moe_sum_rows(rows, inverse, held, segment, *, segments: int):
    """``rows`` [T * k, D], the buffer sorted by ``segment`` and then by
    pick; ``inverse`` [T * k] a pick's row, ``held`` [T, k], ``segment``
    [T * k] a pick's segment of ``segments`` (the picks that are not held
    in the last) -> [T, D]: each token the float32 sum of its held picks'
    rows, rounded once to ``rows.dtype``; what
    ``parallel/moe._sum_held_rows_xla`` computes, as one Mosaic call.

    Mosaic moves no less than 8 rows of the buffer at once (a row is a
    sublane of D / 128 tiles, and a slice of a tiled dimension is whole
    tiles), and the sort is what makes that enough: a tile of 128 tokens
    needs ``segments`` runs of consecutive rows, fetched in
    ``tile * k / 8 + segments`` copies of 8 rows (one copy costs 17 cycles
    whatever its size: a copy a row on the buffer laid ``[T * k, D / 128,
    128]`` took 2.44 ms for 131,072 rows, and XLA 1.63 more to lay it so),
    and the rows reach their tokens through the MXU.  So the gathered
    ``[T * k, D]`` copy, its re-read by the sum and, where k does not fill
    a sublane tile, its relayout are never made: 1.42 ms a call at 131,072
    rows of 2,048 on the v5e against 5.20 (1.76 against 9.72 at top-10),
    the same to 1.4% at one pick in eight held and at all held (PERF.md,
    PR 40).  The caller has asked :func:`_moe_sum_rows_blocks` whether the
    shapes have blocks.  Jitted, so that the forward's and the backward's
    calls of every run of layers share one trace (each cost about a second
    of set-up in every run, warm or cold)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ..parallel.sharding import pcast_to_union

    t, k = held.shape
    d = rows.shape[1]
    groups, vmem = _moe_sum_rows_blocks(t, k, d, segments, rows.dtype)
    g, program = _SUM_ROWS_GROUP, _SUM_ROWS_PROGRAM
    tiles = program // _SUM_ROWS_TILE
    place, fetch, counts = (
        pcast_to_union(a, rows, inverse, held, segment)
        for a in _moe_sum_rows_plan(inverse.reshape(-1), held,
                                    segment.reshape(-1), segments, groups))
    rows = pcast_to_union(rows, place)
    smem = functools.partial(pl.BlockSpec, index_map=lambda i: (i,),
                             memory_space=pltpu.SMEM)
    with kernel_scope("moe_sum_rows"):
        return pl.pallas_call(
            functools.partial(
                _moe_sum_rows_kernel, k=k, groups=groups,
                precision=(jax.lax.Precision.HIGHEST
                           if rows.dtype == jnp.float32 else None)),
            grid=(t // program,),
            in_specs=[smem((fetch.size // (t // program),)),
                      smem((tiles * _SUM_ROWS_WORDS,)),
                      pl.BlockSpec((place.shape[0], program),
                                   lambda i: (0, i)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((program, d), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((t, d), rows.dtype,
                                           **_vma_kw(rows)),
            scratch_shapes=[pltpu.VMEM((2, groups * g, d), rows.dtype),
                            pltpu.VMEM((8, g, d), rows.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",), vmem_limit_bytes=vmem),
            cost_estimate=pl.CostEstimate(
                flops=2 * t * groups * g * d, transcendentals=0,
                bytes_accessed=(rows.size + t * d) * rows.dtype.itemsize),
            interpret=_use_interpret(),
        )(fetch, counts, place, rows)


# ---------------------------------------------------------------------------
# EVA's summaries (``ops/eva.py``): every row of window w attends over the
# ``per`` x w chunk summaries of the windows before its own.  The mask is by
# whole windows, "the summary's window is before the row's", so a q tile is
# one window and sees a prefix of the summaries: tiles of ``_EVA_FWD_TILE``
# / ``_EVA_BWD_TILE`` summaries, the last of a prefix masked by its columns.  Three calls on the
# projections' [B, L, H*D] rows and the summaries' [B, N, H*D], a head a
# program (head_dim whole 128-lane tiles): the forward (out and logsumexp,
# which joins this softmax to the window's), dq, and dk~ / dv~.  The
# backward is two calls of three and four products, not one of five: the
# second score product is a quarter of a small part (the summaries are
# under half of EVA's pairs at 32,768 rows), and neither call needs the
# sequence's dq in VMEM.
# ---------------------------------------------------------------------------

# Summaries a step, the prefix's granule.  On the v5e at [1, 32768, 8, 128]
# bf16, windows of 2,048 and 128 summaries a window, ms a call by the host
# clock over 20 calls (PERF.md, PR 41), tiles of 256 / 512 / 1024 / 2048:
# forward 3.81 / 2.74 / 2.03 / 2.39, forward + dq + dk~dv~ 8.51 / 7.15 / 6.82 /
# 8.24: a larger tile rescales ``acc`` less often and computes more masked
# pairs; the backward's two calls (the difference) are best at 512.
_EVA_FWD_TILE = 1024
_EVA_BWD_TILE = 512


def eva_summary_tiles(rows: int, window: int, per: int, head_dim: int,
                      dtype) -> bool:
    """Whether :func:`eva_summary_attention` has blocks for ``rows`` rows
    in windows of ``window`` with ``per`` summaries a window: a head whole
    lane tiles, a window a block of whole lane tiles (its logsumexp leaves
    as a row), the summaries whole tiles.  Interpret mode has no floor."""
    summaries = rows // window * per
    tiles = [min(t, summaries) for t in (_EVA_FWD_TILE, _EVA_BWD_TILE)]
    if rows % window or any(summaries % t for t in tiles):
        return False
    return _use_interpret() or (
        head_dim % 128 == 0 and window % 128 == 0 and window <= 4096
        and all(t % _sublane_tile(dtype) == 0 for t in tiles))


def _eva_prefix(step, tile: int, seen):
    """(active, whole) of summaries ``step * tile ..`` for rows that see
    the first ``seen``: whether any is seen, and whether all are."""
    return step * tile < seen, (step + 1) * tile <= seen


def _eva_columns_seen(shape, *, axis: int, first, seen):
    """True where the summary, ``first`` + the index along ``axis``, is
    one of the first ``seen``."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis) + first < seen


def _eva_summary_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_s, m_s, l_s,
                        *, scale: float, per: int, rows: int):
    """Forward, grid (b, head, window, summary tile), the tile innermost:
    online softmax over the window's prefix of the summaries.  A row of
    the first window sees none: 0 and a logsumexp of -1e30."""
    import jax.experimental.pallas as pl

    iq, it = pl.program_id(2), pl.program_id(3)
    tile = k_ref.shape[1]
    seen = iq * per

    @pl.when(it == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    def _tile(whole: bool):
        for r in range(0, q_ref.shape[1], rows):
            chunk = pl.ds(r, rows)
            s = jax.lax.dot_general(
                q_ref[0, chunk, :], k_ref[0, :, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            # Tile 0 comes first and every row sees its first summaries.
            _online_softmax_update(
                s, v_ref[0, :, :], acc_s.at[chunk], m_s.at[chunk],
                l_s.at[chunk], None if whole else _eva_columns_seen(
                    s.shape, axis=1, first=it * tile, seen=seen))

    active, whole = _eva_prefix(it, tile, seen)
    pl.when(whole)(lambda: _tile(True))
    pl.when(jnp.logical_and(active, jnp.logical_not(whole)))(
        lambda: _tile(False))

    @pl.when(it == pl.num_programs(3) - 1)
    def _flush():
        l = jnp.maximum(l_s[...], 1e-30)
        o_ref[0, :, :] = (acc_s[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0, :, :] = _column_to_row(m_s[...] + jnp.log(l))


def _eva_summary_cotangents(k, v, q, do, lse, delta, scale: float, seen):
    """(p^T, ds^T) [summaries, rows] of one tile of summaries ``k``, ``v``
    against a chunk of rows: the score tile transposed as the local
    backward's, so that the two row statistics ``lse`` and ``delta`` [1,
    rows] enter as lane-dense rows.  ``seen`` masks the summaries (axis 0)
    a prefix ends before; None where the tile is whole."""
    nt = (((1,), (1,)), ((), ()))
    st = jax.lax.dot_general(k, q, nt,
                             preferred_element_type=jnp.float32) * scale
    if seen is not None:
        st = jnp.where(seen(st.shape), st, _NEG_INF)
    pt = jnp.exp(st - lse)
    dpt = jax.lax.dot_general(v, do, nt, preferred_element_type=jnp.float32)
    return pt, (pt * (dpt - delta)).astype(q.dtype)


def _eva_summary_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                           dq_ref, dq_s, *, scale: float, per: int,
                           rows: int):
    """dq, on the forward's grid: the score tile transposed [summaries,
    rows] as the local backward's, so the two row statistics enter as
    lane-dense rows."""
    import jax.experimental.pallas as pl

    iq, it = pl.program_id(2), pl.program_id(3)
    tile = k_ref.shape[1]
    seen = iq * per

    @pl.when(it == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    def _tile(whole: bool):
        k = k_ref[0, :, :]
        for r in range(0, q_ref.shape[1], rows):
            chunk = pl.ds(r, rows)
            _, dst = _eva_summary_cotangents(
                k, v_ref[0, :, :], q_ref[0, chunk, :], do_ref[0, chunk, :],
                lse_ref[0, 0, :, r:r + rows], dl_ref[0, 0, :, r:r + rows],
                scale, None if whole else functools.partial(
                    _eva_columns_seen, axis=0, first=it * tile, seen=seen))
            dq_s[chunk, :] += jax.lax.dot_general(
                dst, k, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    active, whole = _eva_prefix(it, tile, seen)
    pl.when(whole)(lambda: _tile(True))
    pl.when(jnp.logical_and(active, jnp.logical_not(whole)))(
        lambda: _tile(False))

    @pl.when(it == pl.num_programs(3) - 1)
    def _flush():
        dq_ref[0, :, :] = (dq_s[...] * scale).astype(dq_ref.dtype)


def _eva_summary_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                            dk_ref, dv_ref, dk_s, dv_s, *, scale: float,
                            per: int, rows: int, windows: int):
    """dk~ and dv~, grid (b, head, summary tile, window), the window
    innermost: the tile stays while the windows that see any of it stream
    past, from the one after its first summary's on."""
    import jax.experimental.pallas as pl

    it, step = pl.program_id(2), pl.program_id(3)
    tile = k_ref.shape[1]
    iq = (it * tile) // per + 1 + step
    nn = (((1,), (0,)), ((), ()))

    @pl.when(step == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    def _tile(whole: bool):
        for r in range(0, q_ref.shape[1], rows):
            chunk = pl.ds(r, rows)
            q, do = q_ref[0, chunk, :], do_ref[0, chunk, :]
            pt, dst = _eva_summary_cotangents(
                k_ref[0, :, :], v_ref[0, :, :], q, do,
                lse_ref[0, 0, :, r:r + rows], dl_ref[0, 0, :, r:r + rows],
                scale, None if whole else functools.partial(
                    _eva_columns_seen, axis=0, first=it * tile,
                    seen=iq * per))
            dv_s[...] += jax.lax.dot_general(
                pt.astype(do.dtype), do, nn,
                preferred_element_type=jnp.float32)
            dk_s[...] += jax.lax.dot_general(
                dst, q, nn, preferred_element_type=jnp.float32)

    whole = (it + 1) * tile <= iq * per
    inside = iq < windows
    pl.when(jnp.logical_and(inside, whole))(lambda: _tile(True))
    pl.when(jnp.logical_and(inside, jnp.logical_not(whole)))(
        lambda: _tile(False))

    @pl.when(step == pl.num_programs(3) - 1)
    def _flush():
        dk_ref[0, :, :] = (dk_s[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, :, :] = dv_s[...].astype(dv_ref.dtype)


def _eva_summary_plan(q, ks, window: int, per: int, heads: int, most: int):
    """What the calls on the forward's grid (b, head, window, summary tile)
    share: (the grid, the block specs of a window's rows, of a summary tile
    and of a row statistic, the summary tile, the kernels' settings)."""
    import jax.experimental.pallas as pl

    d = q.shape[2] // heads
    windows = q.shape[1] // window
    tile = min(most, ks.shape[1])
    steps = max(-(-(windows - 1) * per // tile), 1)

    def prefix_tile(bb, hh, qq, tt):
        # Past the window's prefix: the tile that is already resident.
        return (bb, jnp.minimum(tt, jnp.maximum(qq * per - 1, 0) // tile),
                hh)

    rows = pl.BlockSpec((1, window, d), lambda bb, hh, qq, tt: (bb, qq, hh))
    stat = pl.BlockSpec((1, 1, 1, window),
                        lambda bb, hh, qq, tt: (bb, hh, 0, qq))
    return ((q.shape[0], heads, windows, steps), rows,
            pl.BlockSpec((1, tile, d), prefix_tile), stat, tile,
            dict(scale=d ** -0.5, per=per, rows=_chunk_rows(window)))


def _eva_summary_fwd_call(q, ks, vs, *, heads: int, window: int, per: int):
    """q [B, L, H*D], ks / vs [B, N, H*D] -> (out [B, L, H*D] in q's dtype,
    lse [B, H, 1, L] f32)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    grid, rows, summaries, stat, _, settings = _eva_summary_plan(
        q, ks, window, per, heads, _EVA_FWD_TILE)
    d = q.shape[2] // heads
    kw = _vma_kw(q, ks, vs)
    with kernel_scope("eva_sum_fwd"):
        return pl.pallas_call(
            functools.partial(_eva_summary_kernel, **settings),
            grid=grid, in_specs=[rows, summaries, summaries],
            out_specs=[rows, stat],
            out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype, **kw),
                       jax.ShapeDtypeStruct(
                           (q.shape[0], heads, 1, q.shape[1]), jnp.float32,
                           **kw)),
            scratch_shapes=[pltpu.VMEM((window, d), jnp.float32),
                            pltpu.VMEM((window, 1), jnp.float32),
                            pltpu.VMEM((window, 1), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_FWD_VMEM_LIMIT),
            interpret=_use_interpret(),
        )(q, ks, vs)


def _eva_summary_bwd_calls(q, ks, vs, do, lse, delta, *, heads: int,
                           window: int, per: int):
    """-> (dq [B, L, H*D], dk~, dv~ [B, N, H*D]) in the operands' dtypes;
    lse and delta f32 rows [B, H, 1, L]."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    grid, rows, summaries, stat, tile, settings = _eva_summary_plan(
        q, ks, window, per, heads, _EVA_BWD_TILE)
    b, _, windows, _ = grid
    d = q.shape[2] // heads
    kw = _vma_kw(q, ks, vs, do, lse, delta)
    params = pltpu.CompilerParams(vmem_limit_bytes=_FWD_VMEM_LIMIT)
    with kernel_scope("eva_sum_dq"):
        dq = pl.pallas_call(
            functools.partial(_eva_summary_dq_kernel, **settings),
            grid=grid,
            in_specs=[rows, summaries, summaries, rows, stat, stat],
            out_specs=rows,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype, **kw),
            scratch_shapes=[pltpu.VMEM((window, d), jnp.float32)],
            compiler_params=params, interpret=_use_interpret(),
        )(q, ks, vs, do, lse, delta)

    def window_seen(tt, ss):
        # Past the last window: the one that is already resident.
        return jnp.minimum((tt * tile) // per + 1 + ss, windows - 1)

    rows = pl.BlockSpec(
        (1, window, d), lambda bb, hh, tt, ss: (bb, window_seen(tt, ss), hh))
    stat = pl.BlockSpec(
        (1, 1, 1, window),
        lambda bb, hh, tt, ss: (bb, hh, 0, window_seen(tt, ss)))
    summaries = pl.BlockSpec((1, tile, d),
                             lambda bb, hh, tt, ss: (bb, tt, hh))
    with kernel_scope("eva_sum_dkv"):
        dk, dv = pl.pallas_call(
            functools.partial(_eva_summary_dkv_kernel, windows=windows,
                              **settings),
            grid=(b, heads, ks.shape[1] // tile, max(windows - 1, 1)),
            in_specs=[rows, summaries, summaries, rows, stat, stat],
            out_specs=[summaries, summaries],
            out_shape=(jax.ShapeDtypeStruct(ks.shape, ks.dtype, **kw),
                       jax.ShapeDtypeStruct(vs.shape, vs.dtype, **kw)),
            scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32),
                            pltpu.VMEM((tile, d), jnp.float32)],
            compiler_params=params, interpret=_use_interpret(),
        )(q, ks, vs, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _eva_summary_diff(q, ks, vs, window, per):
    b, l, h, d = q.shape
    out, lse = _eva_summary_fwd_call(
        *(x.reshape(x.shape[0], x.shape[1], h * d) for x in (q, ks, vs)),
        heads=h, window=window, per=per)
    return out.reshape(q.shape), lse


def _eva_summary_diff_fwd(q, ks, vs, window, per):
    out, lse = _eva_summary_diff(q, ks, vs, window, per)
    return (out, lse), (q, ks, vs, out, lse)


def _eva_summary_diff_bwd(window, per, res, cotangents):
    q, ks, vs, out, lse = res
    do, dlse = cotangents
    b, l, h, d = q.shape
    # d lse / d s = p: the score's cotangent p (dp - delta) takes delta -
    # dlse for delta = rowsum(dO * out).
    delta = jnp.einsum("bqhd,bqhd->bhq", do, out,
                       preferred_element_type=jnp.float32)[:, :, None, :] \
        - dlse
    dq, dk, dv = _eva_summary_bwd_calls(
        *(x.reshape(x.shape[0], x.shape[1], h * d) for x in (q, ks, vs, do)),
        lse, delta, heads=h, window=window, per=per)
    return dq.reshape(q.shape), dk.reshape(ks.shape), dv.reshape(vs.shape)


_eva_summary_diff.defvjp(_eva_summary_diff_fwd, _eva_summary_diff_bwd)


def eva_summary_attention(q: jax.Array, ks: jax.Array, vs: jax.Array, *,
                          window: int, per: int):
    """Softmax attention of every row of q [B, L, H, D] over the summaries
    ks / vs [B, N, H, D] of the windows before its own (``per`` a window of
    ``window`` rows), and its logsumexp, both differentiable: ``(out [B, L,
    H, D] in q's dtype, lse [B, H, L] f32)``; the first window's rows see
    nothing and get 0 and -1e30.  For shapes :func:`eva_summary_tiles`
    takes."""
    out, lse = _eva_summary_diff(q, ks, vs, window, per)
    return out, lse[:, :, 0, :]


def attention_reference(q, k, v, *, causal=True, scale=None,
                        with_lse=False, window=None, block_diffusion=None):
    """Naive jnp attention (materializes scores) — the correctness oracle.
    ``with_lse`` also returns the f32 logsumexp of the scaled, masked
    scores, [B, H, Lq]: the statistic the flash forwards save.  ``window``
    and ``block_diffusion`` as :func:`flash_attention`'s."""
    b, lq, h, d = q.shape
    hkv = k.shape[2]
    if scale is None:
        scale = d ** -0.5
    if h != hkv:
        k = jnp.repeat(k, h // hkv, axis=2)
        v = jnp.repeat(v, h // hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if block_diffusion is not None:
        s = jnp.where(block_diffusion_mask(lq, block_diffusion)[None, None],
                      s, _NEG_INF)
    elif causal:
        lk = k.shape[1]
        mask = _visible_mask(
            jnp.arange(lq)[:, None] - jnp.arange(lk)[None, :], 0, window)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p,
                     v.astype(jnp.float32)).astype(q.dtype)
    if with_lse:
        return out, jax.nn.logsumexp(s, axis=-1)
    return out
