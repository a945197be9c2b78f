"""The Mamba-2 mixer: a state-space layer whose state decays by one scalar
a head a token, computed in chunks (the state-space dual form,
arXiv:2405.21060).

Per head, with a state ``S`` [head_dim, state] that starts at 0, a time
step ``delta_t`` > 0 and a decay ``a_t = exp(A delta_t)`` in (0, 1] (``A``
< 0, one number a head):

    S_t = a_t S_{t-1} + delta_t x_t B_t^T;   y_t = S_t C_t

``B_t`` and ``C_t`` [state] are shared by the heads of a group (all of them
where there is one group).  :func:`ssd_scan` runs the same in chunks of
``chunk`` tokens, ``l_i`` the running sum of ``log a`` inside the chunk and
``S_0`` the state entering it:

    y_i = sum_{j<=i} (C_i . B_j) exp(l_i - l_j) delta_j x_j
          + exp(l_i) S_0 C_i
    S_C = exp(l_C) S_0 + sum_j exp(l_C - l_j) delta_j x_j B_j^T

so that the work inside a chunk is batched matrix products and only the
state's pass from chunk to chunk is sequential.  The score matrix ``C B^T``
of a chunk is computed once a group and masked by each head's decays.

What stays float32 whatever the compute dtype: ``delta``, the log-decays
and their running sum (an exponent: its absolute error is the result's
relative one), every ratio of decays (the exp of a difference, never a
quotient), the states carried from chunk to chunk, and ``y``.  The
products take operands in the compute dtype and accumulate in float32
(``delta_j x_j`` and ``exp(l_C - l_j) delta_j x_j`` are made in float32 and
rounded once, as operands); the masked entries are exactly 0.

Three parts, one scope each under ``hvdt.ssd.scan``, which together
account for all of it.  ``.chunk``: what is computed for all the chunks at
once before the state's loop (``delta``, the running sums, each chunk's own
contribution to the state).  ``.state``: the ``lax.scan`` over the chunks
that carries ``S``.  ``.out``: what is computed for all the chunks at once
after it: the scores, the masked products inside the chunks, what the
entering states add, ``D x``, their sum ``y``.

What is computed for all the chunks at once is two functions with
differentiation rules written by hand (:func:`_chunk_state` before the
loop, :func:`_chunk_out` after it; the loop between them is JAX's to
differentiate): a rule keeps its inputs and nothing [C, C], and its
backward makes the pairs again.  Each rule has two schedules, and
:func:`_scan_on_kernels` chooses from what it can see, the platform and
the shapes, with no knob.  **Mosaic's** (``pallas_kernels.ssd_chunk_*``,
four calls under ``hvdt.kernel.ssd_chunk_state`` / ``_out`` / ``_state_bwd``
/ ``_out_bwd``) on a TPU for whole chunks of whole lane tiles, one group,
heads in blocks of 8 and a state of whole lane tiles: a program holds a
chunk and 8 heads, reads x, B and C on the convolution's own token-major
rows (column blocks, no head-major copy), makes a head's [C, C] pairs in
VMEM and drops them there, and writes ``y`` once, token-major [B, L, H P],
with ``D x`` in it.  **XLA's** everywhere else (the CPU, a padded tail,
more groups, a chunk or a state under 128): batched einsums over (batch,
chunk, head) with the pairs in HBM, the kernels' oracle.  On the v5e for
one layer of ``granite_h_micro_s8192`` (B 1, L 8192, 64 heads of 64, state
128, chunks of 256; device events, my chip runs, PR 46, PERF.md section
6), ms a call of state / out / state's backward / out's backward:
Mosaic's 0.38 / 0.54 / 0.52 / 1.61, XLA's schedule of the same rules 1.44
/ 4.87 / 4.04 / 7.15; in the cell's step ``hvdt.ssd.scan`` went from
125.0 ms (XLA's form differentiated pass by pass) to 44.1.

:func:`mamba2_mixer` is the whole mixer as the model calls it (the input
projection, a causal depthwise convolution with a bias, the scan, the
gated norm over a group's channels, the output projection), each part
under its own scope (``hvdt.ssd.proj`` / ``.conv`` / ``.scan`` /
``.norm``; the model opens ``hvdt.ssd`` around the call and its pre-norm).
"""

from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp
from jax import lax

from . import gated_delta, pallas_kernels
from .gated_delta import causal_conv, gated_rmsnorm

__all__ = ["ssd_scan", "mamba2_mixer", "scan_macs_per_token"]


def _scan_on_kernels(rows: int, dims, chunk: int) -> bool:
    """Which schedule of the chunk passes and of their rule runs: on a TPU
    (``gated_delta._on_tpu``: the kernels' own reading of the platform),
    for a length and sizes the kernels tile (``pallas_kernels.
    ssd_chunk_tiles``: whole chunks of whole lane tiles, one group, heads
    in blocks of 8 that fill whole lane tiles, a state of whole lane
    tiles), Mosaic's; elsewhere, the CPU and a padded tail among it,
    XLA's.  Read from the platform and the shapes; no knob."""
    return gated_delta._on_tpu() and pallas_kernels.ssd_chunk_tiles(
        rows, dims, chunk)


def _chunked(xbc, delta, l, dims, chunk: int):
    """The rows [B, N C, I + 2 G S] as x [B,N,C,G,R,P] and B, C
    [B,N,C,G,S]; delta and l [B, N C, H] as [B,N,C,G,R]."""
    h, p, g, s = dims
    bsz, n = xbc.shape[0], xbc.shape[1] // chunk
    inner, bc = h * p, g * s
    small = (bsz, n, chunk, g, h // g)
    return (xbc[..., :inner].reshape(small + (p,)),
            xbc[..., inner:inner + bc].reshape(bsz, n, chunk, g, s),
            xbc[..., inner + bc:].reshape(bsz, n, chunk, g, s),
            delta.reshape(small), l.reshape(small))


def _as_rows(x, b, c):
    """Chunked x, B, C (or their cotangents) back as rows [B, N C, I + 2 G
    S]."""
    flat = x.shape[:1] + (x.shape[1] * x.shape[2], -1)
    return jnp.concatenate([t.reshape(flat) for t in (x, b, c)], -1)


def _state_fwd_jax(xbc, delta, l, dims, chunk: int):
    """XLA's schedule of the pass before the state's loop: each chunk's
    own contribution to the state leaving it, ``sum_j exp(l_C - l_j)
    delta_j x_j B_j^T``, [N, B, H P, S] in float32."""
    f32, dt = jnp.float32, xbc.dtype
    x, b, _, delta, l = _chunked(xbc, delta, l, dims, chunk)
    to_end = delta * jnp.exp(l[:, :, -1:] - l)          # [B,N,C,G,R]
    own = jnp.einsum("bnjgrp,bnjgs->nbgrps",
                     (x.astype(f32) * to_end[..., None]).astype(dt), b,
                     preferred_element_type=f32)
    return own.reshape(own.shape[:2] + (-1, own.shape[-1]))


def _state_bwd_jax(xbc, delta, l, d_own, dims, chunk: int):
    """XLA's schedule of that pass's backward: the cotangents of (xbc,
    delta, l) from the states'.  The states' cotangent enters the two
    products in the compute dtype; what reaches delta and l goes through
    ``to_end_j = delta_j exp(l_C - l_j)``, in float32."""
    f32, dt = jnp.float32, xbc.dtype
    h, p, g, s = dims
    x, b, c, delta, l = _chunked(xbc, delta, l, dims, chunk)
    xf = x.astype(f32)
    w = jnp.exp(l[:, :, -1:] - l)
    to_end = delta * w
    d_own = d_own.reshape(d_own.shape[:2] + (g, h // g, p, s)).astype(dt)
    d_xw = jnp.einsum("bnjgs,nbgrps->bnjgrp", b, d_own,
                      preferred_element_type=f32)
    d_b = jnp.einsum("bnjgrp,nbgrps->bnjgs",
                     (xf * to_end[..., None]).astype(dt), d_own,
                     preferred_element_type=f32)
    d_to_end = (d_xw * xf).sum(-1)
    through = d_to_end * to_end
    d_l = (-through).at[:, :, -1].add(through.sum(2))
    d_x = (d_xw * to_end[..., None]).astype(dt)
    whole = delta.shape[:1] + (-1, h)
    return (_as_rows(d_x, d_b.astype(dt), jnp.zeros_like(c)),
            (d_to_end * w).reshape(whole), d_l.reshape(whole))


def _pairs(b, c, l):
    """``C B^T`` of a chunk, once a group, [B,N,G,1,C,C] in float32, and
    each head's ``exp(l_i - l_j)`` for j <= i, exactly 0 above the
    diagonal, [B,N,G,R,C,C]."""
    chunk = l.shape[2]
    scores = jnp.einsum("bnigs,bnjgs->bngij", c, b,
                        preferred_element_type=jnp.float32)
    rows = jnp.moveaxis(l, 2, -1)                       # [B,N,G,R,C]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    ratio = jnp.exp(jnp.where(
        lower, rows[..., :, None] - rows[..., None, :], -jnp.inf))
    return scores[:, :, :, None], ratio


def _out_fwd_jax(xbc, delta, l, s_in, skip, dims, chunk: int):
    """XLA's schedule of the pass after the state's loop: y [B, N C, H P]
    in float32 from the masked pairs' product with ``delta x``, what the
    states entering the chunks ``s_in`` [N, B, H P, S] add, and ``D x``
    (``skip`` [1, H P]: D a lane)."""
    f32, dt = jnp.float32, xbc.dtype
    h, p, g, s = dims
    x, b, c, delta, l = _chunked(xbc, delta, l, dims, chunk)
    xf = x.astype(f32)
    scores, ratio = _pairs(b, c, l)
    y_own = jnp.einsum("bngrij,bnjgrp->bnigrp", (scores * ratio).astype(dt),
                       (xf * delta[..., None]).astype(dt),
                       preferred_element_type=f32)
    y_in = jnp.einsum("bnigs,nbgrps->bnigrp", c,
                      s_in.reshape(s_in.shape[:2] + (g, h // g, p, s)),
                      preferred_element_type=f32)
    y = (y_own + jnp.exp(l)[..., None] * y_in
         + skip.reshape(g, h // g, p) * xf)
    return y.reshape(xbc.shape[:2] + (h * p,))


def _out_bwd_jax(xbc, delta, l, s_in, skip, d_y, dims, chunk: int):
    """XLA's schedule of that pass's backward: the cotangents of (xbc,
    delta, l, s_in, skip) from y's, the pairs made again.  y's cotangent
    enters the products in the compute dtype.  What reaches l through the
    pairs is ``pairs_ij d(pairs)_ij`` summed over a token's row less the
    same summed over its column: one array summed two ways, so that a
    chunk's shares cancel as ratios of decays say they must (a sum of
    8,192 of them that did not cancel to rounding read cosines of 0.54 to
    0.85 on ``a_log`` and ``dt_bias`` in granite_h_micro_s8192; PERF.md,
    PR 46).  The kernels sum the same terms over a head's channels
    (``dy_i . y_own_i`` less ``(delta x)_j . d(delta x)_j`` on the
    products' own rounded operands) and so make no [C, C] sum; XLA, which
    may keep a rounded operand's excess precision, sums the array.  Through
    ``exp(l_i)`` it is ``dy_i . exp(l_i) y_in_i``, and delta's is ``x_j .
    d(delta x)_j``."""
    f32, dt = jnp.float32, xbc.dtype
    h, p, g, s = dims
    x, b, c, delta, l = _chunked(xbc, delta, l, dims, chunk)
    xf = x.astype(f32)
    heads = (g, h // g, p)
    d_y = d_y.reshape(x.shape)
    d_yb = d_y.astype(dt)
    s_in = s_in.reshape(s_in.shape[:2] + heads + (s,))
    skip = skip.reshape(heads)
    scores, ratio = _pairs(b, c, l)
    m = (scores * ratio).astype(dt)
    xd = (xf * delta[..., None]).astype(dt)
    e = jnp.exp(l)[..., None]
    y_in = jnp.einsum("bnigs,nbgrps->bnigrp", c, s_in,
                      preferred_element_type=f32)
    d_xd = jnp.einsum("bngrij,bnigrp->bnjgrp", m, d_yb,
                      preferred_element_type=f32)
    d_m = jnp.einsum("bnigrp,bnjgrp->bngrij", d_yb, xd,
                     preferred_element_type=f32)
    d_scores = (d_m * ratio).sum(3).astype(dt)          # [B,N,G,C,C]
    e_dy = (d_y * e).astype(dt)
    d_c = (jnp.einsum("bngij,bnjgs->bnigs", d_scores, b,
                      preferred_element_type=f32)
           + jnp.einsum("bnigrp,nbgrps->bnigs", e_dy, s_in,
                        preferred_element_type=f32))
    d_b = jnp.einsum("bngij,bnigs->bnjgs", d_scores, c,
                     preferred_element_type=f32)
    d_s = jnp.einsum("bnigrp,bnigs->nbgrps", e_dy, c,
                     preferred_element_type=f32).astype(dt)
    d_delta = (xf * d_xd).sum(-1)
    through = d_m * (scores * ratio)                    # [B,N,G,R,C,C]
    d_l = (jnp.moveaxis(through.sum(-1) - through.sum(-2), -1, 2)
           + (d_y * e * y_in).sum(-1))
    d_x = (d_xd * delta[..., None] + skip * d_y).astype(dt)
    whole = delta.shape[:1] + (-1, h)
    return (_as_rows(d_x, d_b.astype(dt), d_c.astype(dt)),
            d_delta.reshape(whole), d_l.reshape(whole),
            d_s.reshape(d_s.shape[:2] + (h * p, s)),
            (d_y * xf).sum((0, 1, 2)).reshape(1, h * p))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _chunk_state(xbc, delta, l, dims, chunk, kernels):
    """What :func:`ssd_scan` computes for all the chunks at once before
    the state's loop, as one function with a differentiation rule of its
    own: from the rows ``xbc`` [B, N C, I + 2 G S] (columns [x | B | C],
    the compute dtype), delta and the running log-decay ``l`` [B, N C, H]
    (float32) to each chunk's own contribution to the state, [N, B, H P,
    S] in float32 (``dims`` = (H, P, G, S)).  One rule, two schedules of
    it (``kernels``, :func:`_scan_on_kernels`'s answer): XLA's
    (:func:`_state_fwd_jax`, :func:`_state_bwd_jax`) and Mosaic's
    (``pallas_kernels.ssd_chunk_state`` / ``ssd_chunk_state_bwd``)."""
    return _chunk_state_fwd(xbc, delta, l, dims, chunk, kernels)[0]


def _chunk_state_fwd(xbc, delta, l, dims, chunk, kernels):
    fwd = pallas_kernels.ssd_chunk_state if kernels else _state_fwd_jax
    return fwd(xbc, delta, l, dims, chunk), (xbc, delta, l)


def _chunk_state_bwd(dims, chunk, kernels, res, d_own):
    # JAX names the rule's equations after the forward's call
    # (transpose(jvp(.. hvdt.ssd.scan.chunk))): no second scope here.
    bwd = pallas_kernels.ssd_chunk_state_bwd if kernels else _state_bwd_jax
    return bwd(*res, d_own, dims, chunk)


_chunk_state.defvjp(_chunk_state_fwd, _chunk_state_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _chunk_out(xbc, delta, l, s_in, skip, dims, chunk, kernels):
    """What :func:`ssd_scan` computes for all the chunks at once after the
    state's loop, under the same kind of rule: from the rows, delta, ``l``,
    the states entering the chunks ``s_in`` [N, B, H P, S] (the compute
    dtype) and ``D`` a lane ``skip`` [1, H P] (float32) to y [B, N C, H P]
    in float32, ``D x`` in it.  The rule keeps its five inputs and nothing
    [C, C]: the backward makes the pairs again.  XLA's schedule
    (:func:`_out_fwd_jax`, :func:`_out_bwd_jax`) and Mosaic's
    (``pallas_kernels.ssd_chunk_out`` / ``ssd_chunk_out_bwd``: a program
    holds a chunk and 8 heads, the pairs live and die in VMEM, y is
    written once)."""
    return _chunk_out_fwd(xbc, delta, l, s_in, skip, dims, chunk, kernels)[0]


def _chunk_out_fwd(xbc, delta, l, s_in, skip, dims, chunk, kernels):
    fwd = pallas_kernels.ssd_chunk_out if kernels else _out_fwd_jax
    return (fwd(xbc, delta, l, s_in, skip, dims, chunk),
            (xbc, delta, l, s_in, skip))


def _chunk_out_bwd(dims, chunk, kernels, res, d_y):
    bwd = pallas_kernels.ssd_chunk_out_bwd if kernels else _out_bwd_jax
    return bwd(*res, d_y, dims, chunk)


_chunk_out.defvjp(_chunk_out_fwd, _chunk_out_bwd)


def _scan_rows(xbc: jax.Array, delta: jax.Array, a: jax.Array,
               skip: jax.Array, dims, *, chunk: int) -> jax.Array:
    """:func:`ssd_scan` on the convolution's own rows: ``xbc`` [B, L, I +
    2 G S] with the columns [x | B | C], a head a column block, delta
    [B, L, H] float32, ``a`` [H], ``skip`` [1, H P] (``D`` a lane), ``dims``
    = (H, P, G, S).  Returns y [B, L, H P] in float32 with ``D x`` in it.
    The mixer calls this where the kernels run, so that no copy of x, B or
    C is made in front of them."""
    h, p, _, s = dims
    bsz, length, _ = xbc.shape
    dt, f32 = xbc.dtype, jnp.float32
    kernels = _scan_on_kernels(length, dims, chunk)
    pad = (-length) % chunk
    n = (length + pad) // chunk

    def padded(t):
        return jnp.pad(t, ((0, 0), (0, pad), (0, 0))) if pad else t

    with jax.named_scope("hvdt.ssd.scan.chunk"):
        xbc, delta = padded(xbc), padded(delta.astype(f32))
        # l_i: the running sum of log a inside the chunk
        l = jnp.cumsum((delta * a.astype(f32)).reshape(bsz, n, chunk, h),
                       axis=2)
        decayed = jnp.moveaxis(jnp.exp(l[:, :, -1]), 1, 0)      # [N, B, H]
        l = l.reshape(delta.shape)
        own = _chunk_state(xbc, delta, l, dims, chunk, kernels)
        xs = (own.reshape(n, bsz, h, p, s), decayed)
        s0 = jnp.zeros((bsz, h, p, s), f32)
        # Inside a shard_map the operands are varying over its axes and
        # so is the state the body returns: the initial state has to
        # match, and so has a parameter beside the rows in one call.
        from ..parallel.sharding import pcast_to_union
        s0, skip = pcast_to_union(s0, *xs), pcast_to_union(skip, *xs)

    def step(state, chunk_of):          # state [B, H, P, S] float32
        own, decayed = chunk_of
        return decayed[..., None, None] * state + own, state.astype(dt)

    with jax.named_scope("hvdt.ssd.scan.state"):
        _, s_in = lax.scan(step, s0, xs)
    with jax.named_scope("hvdt.ssd.scan.out"):
        y = _chunk_out(xbc, delta, l, s_in.reshape(n, bsz, h * p, s), skip,
                       dims, chunk, kernels)
        return y[:, :length]


def ssd_scan(x: jax.Array, delta: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, *, chunk: int) -> jax.Array:
    """The scalar-decay state-space scan over whole sequences, in chunks.

    x: [B, L, H, P] (the compute dtype); delta: [B, L, H] float32, >= 0;
    a: [H] float32, < 0 (the decay of head h at token t is ``exp(a_h
    delta_t,h)``); b, c: [B, L, G, N] with ``G`` dividing ``H`` (head n
    reads group ``n // (H / G)``).  Returns y [B, L, H, P] in float32,
    without ``D x``.  A length that is not whole chunks is padded at its
    end with tokens that neither decay nor write (delta = 0) and whose
    outputs are dropped.  What is carried along the sequence, the running
    sum of the log-decays inside a chunk and the state from chunk to
    chunk, is float32.

    What is computed for all the chunks at once, on both sides of the
    state's loop, is two functions with differentiation rules written by
    hand (:func:`_chunk_state`, :func:`_chunk_out`): their residuals are
    their inputs, and the backward makes the [C, C] pairs again.  Each
    rule has two schedules, chosen by :func:`_scan_on_kernels` from the
    platform and the shapes: Mosaic calls on a TPU for the shapes they
    tile, XLA's einsums elsewhere (the CPU path and the kernels' oracle).
    The state's loop is a ``lax.scan`` in both, differentiated by JAX.
    The mixer reaches the same through :func:`_scan_rows` on the
    convolution's own rows; this form concatenates them."""
    bsz, length, h, p = x.shape
    dims = (h, p) + b.shape[2:]
    with jax.named_scope("hvdt.ssd.scan.chunk"):
        xbc = jnp.concatenate([t.reshape(bsz, length, -1)
                               for t in (x, b, c)], -1)
        skip = jnp.zeros((1, h * p), jnp.float32)
    y = _scan_rows(xbc, delta, a, skip, dims, chunk=chunk)
    with jax.named_scope("hvdt.ssd.scan.out"):
        return y.reshape(bsz, length, h, p)


def mamba2_mixer(x: jax.Array, p: Dict[str, jax.Array], *, heads: int,
                 head_dim: int, state: int, groups: int, chunk: int,
                 eps: float,
                 proj: Callable[[jax.Array, jax.Array], jax.Array]
                 ) -> jax.Array:
    """The Mamba-2 mixer on x [B, L, d] (already normed).

    ``p``: ``w_in`` [d, 2 I + 2 G N + H] with the columns [z | x | B | C |
    dt] (I = heads x head_dim, G = groups, N = state), ``conv`` [taps, I +
    2 G N] over the channels [x | B | C] and, where the convolution has a
    bias, ``conv_bias`` [I + 2 G N]; ``a_log``, ``d_skip`` and ``dt_bias``
    [heads]; ``ssd_norm`` [I]; ``w_out`` [I, d].  ``proj`` is the model's
    dense projection (``x @ w`` in the compute dtype).  The gated norm is
    ``RMS(y silu(z)) w`` over a group's I / G channels, eps ``eps``."""
    bsz, l, _ = x.shape
    inner, bc = heads * head_dim, groups * state
    f32 = jnp.float32
    with jax.named_scope("hvdt.ssd.proj"):
        # Products on the matrix's column blocks, so that no [z|x|B|C|dt]
        # row exists: the convolution's and the gate's cotangents would
        # each be padded to its width, in float32.
        z = proj(x, p["w_in"][:, :inner])
        xbc = proj(x, p["w_in"][:, inner:2 * inner + 2 * bc])
        # dt feeds an exponent's argument: float32 out
        dt = x.astype(f32) @ p["w_in"][:, 2 * inner + 2 * bc:].astype(f32)
    with jax.named_scope("hvdt.ssd.conv"):
        xbc = jax.nn.silu(causal_conv(xbc, p["conv"], p.get("conv_bias")))
    with jax.named_scope("hvdt.ssd.scan"):
        # with the scan's own work before and after its loop, so that the
        # three children account for all of hvdt.ssd.scan
        with jax.named_scope("hvdt.ssd.scan.chunk"):
            delta = jax.nn.softplus(dt + p["dt_bias"].astype(f32))
            a = -jnp.exp(p["a_log"].astype(f32))
            d_skip = p["d_skip"].astype(f32)
        dims = (heads, head_dim, groups, state)
        if _scan_on_kernels(l, dims, chunk):
            # Mosaic's schedule: x, B and C read on the rows' own column
            # blocks, D x inside the call, y token-major
            with jax.named_scope("hvdt.ssd.scan.chunk"):
                skip = jnp.repeat(d_skip, head_dim)[None]
            y = _scan_rows(xbc, delta, a, skip, dims, chunk=chunk)
        else:
            # XLA's: the scan in its [B, L, H, P] form, looked up by its
            # name at the call (the benchmark's tests put a scan without
            # its recurrence in its place), D x a pass of its own
            with jax.named_scope("hvdt.ssd.scan.chunk"):
                xs = xbc[..., :inner].reshape(bsz, l, heads, head_dim)
                b = xbc[..., inner:inner + bc].reshape(bsz, l, groups, state)
                c = xbc[..., inner + bc:].reshape(bsz, l, groups, state)
            y = ssd_scan(xs, delta, a, b, c, chunk=chunk)
            with jax.named_scope("hvdt.ssd.scan.out"):
                y = y + d_skip[:, None] * xs.astype(f32)
    with jax.named_scope("hvdt.ssd.norm"):
        across = (bsz, l, groups, inner // groups)
        y = gated_rmsnorm(y.reshape(across), z.reshape(across),
                          p["ssd_norm"].reshape(across[2:]), eps=eps,
                          gate_first=True).astype(x.dtype)
    with jax.named_scope("hvdt.ssd.proj"):
        return proj(y.reshape(bsz, l, inner), p["w_out"])


def scan_macs_per_token(*, heads: int, head_dim: int, state: int,
                        groups: int, chunk: int) -> float:
    """Forward multiply-adds a token of :func:`ssd_scan`: a group's score
    row (C state), a head's masked product with x (C head_dim), and the
    build and the read of its state (2 head_dim state)."""
    return (groups * chunk * state
            + heads * (chunk * head_dim + 2 * head_dim * state))
