"""The Gated DeltaNet mixer: a linear-attention layer whose state obeys
the gated delta rule, computed in chunks.

Per value head, with a state ``S`` [key_dim, value_dim] that starts at 0,
a decay ``exp(g_t)`` in (0, 1] and a write strength ``beta_t`` in (0, 1):

    S' = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T;   o_t = S_t^T q_t

:func:`gated_delta_rule` runs the same in chunks of ``CHUNK`` tokens, so
that the work inside a chunk is batched matrix products over (batch,
head, chunk) and only the state's pass from chunk to chunk is sequential
(``gamma_i = exp(sum_{j<=i} g_j)`` inside the chunk, ``S_0`` the state
entering it):

    A_ij = beta_i (gamma_i / gamma_j) (k_i . k_j) for j < i, else 0
    T    = (I + A)^-1
    U    = T (beta * V) - T (beta * gamma * K) S_0
    O    = gamma * (Q S_0) + tril((gamma_i / gamma_j) (q_i . k_j)) U
    S_C  = gamma_C S_0 + ((gamma_C / gamma) * K)^T U

What stays float32 whatever the compute dtype: the decays and their
cumulative sum (an exponent: its absolute error is the result's relative
one), every ratio of decays (taken as exp of a difference, never as a
quotient, so nothing overflows where gamma underflows), ``A`` and its
inverse ``T`` (forward substitution row by row: exact where the powers of
``A`` that a Neumann series would sum grow combinatorially, for which
equal neighbouring keys are enough; on a TPU the 64 row steps run in one
Mosaic kernel on blocks of 128 matrices in VMEM, elsewhere as XLA's loop
over the whole array: :func:`_unit_lower_inverse`), and the
state ``S`` carried from chunk to chunk (256 updates a sequence of
16,384 would each round it).  The products take operands in the compute
dtype and accumulate in float32.

Three parts, one scope each under ``hvdt.gdn.scan``.  ``.chunk``: what
is computed for all the chunks at once (the norms, the ratios, ``A``,
``T``, ``T (beta V)``, ``T (beta gamma K)``, ``(gamma_C / gamma) K`` and
the pairs' matrix of ``O``) is one function with a differentiation rule
of its own, :func:`_chunk_passes`: its backward is written by hand from
the inputs and ``T`` (:func:`_chunk_bwd_jax` states it), not derived pass
by pass, and the same rule has two schedules, XLA's everywhere and Mosaic
kernels on a TPU for the shapes they tile (:func:`_chunk_on_kernels`).
``.state``: the ``lax.scan`` over the chunks that carries ``S``.
``.out``: ``O`` from both.

:func:`gated_delta_net` is the whole mixer as the model calls it (the
input projections, a causal depthwise convolution, the rule, the gated
norm a head, the output projection), each part under its own scope
(``hvdt.gdn.proj`` / ``.conv`` / ``.scan`` / ``.norm``; the model opens
``hvdt.gdn`` around the call and its pre-norm).
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import pallas_kernels

__all__ = ["CHUNK", "causal_conv", "gated_delta_rule", "gated_rmsnorm",
           "gated_delta_net", "scan_macs_per_token"]

CHUNK = 64


def causal_conv(x: jax.Array, w: jax.Array,
                bias: Optional[jax.Array] = None, *,
                times: Optional[jax.Array] = None,
                gate: Optional[jax.Array] = None) -> jax.Array:
    """A causal depthwise convolution: x [B, L, C], w [taps, C], ``bias``
    [C] or none; ``y_t = sum_i w[i] x[t - (taps - 1) + i] + bias`` with
    zeros left of the sequence.  Shifted multiply-adds: each tap's slice of
    the padded input is widened to float32 inside the one fusion that sums
    them.  Measured on the v5e at [1, 16384, 8192] bf16 with the silu, ms
    forward / forward + backward (PERF.md, PR 33): this form 2.00 / 7.03;
    the padded input widened to float32 first 5.29 / 11.36; rolls and a
    mask 7.06 / 17.68; ``lax.conv_general_dilated`` with one channel a
    group 6.55 forward.

    ``times`` and ``gate`` [B, L, C], each or both, make it the
    double-gated form ``y_t = gate_t (sum_i w[i] (x times)[t - (taps - 1) +
    i] + bias)``: the input's gate multiplies each tap's slice and the
    output's the sum, both in float32 inside that same fusion, so that
    neither ``x times`` nor the ungated sum exists as an array."""
    taps, l = w.shape[0], x.shape[1]
    left = ((0, 0), (taps - 1, 0), (0, 0))
    padded = jnp.pad(x, left)
    w = w.astype(jnp.float32)
    if times is None:
        y = sum(padded[:, i:i + l].astype(jnp.float32) * w[i]
                for i in range(taps))
    else:
        with_it = jnp.pad(times, left)
        y = sum(padded[:, i:i + l].astype(jnp.float32)
                * with_it[:, i:i + l].astype(jnp.float32) * w[i]
                for i in range(taps))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if gate is not None:
        y = y * gate.astype(jnp.float32)
    return y.astype(x.dtype)


def _on_tpu() -> bool:
    """The kernels' own reading of the platform, so that a rehearsal that
    lowers them through Mosaic also chooses as the chip does."""
    return not pallas_kernels._use_interpret()


def _inverse_on_kernel(c: int) -> bool:
    """Which schedule of the substitution computes ``(I + a)^-1``: the
    Mosaic kernel on a TPU for a chunk it tiles, XLA's loop otherwise (off
    the TPU the kernel would run in the Pallas interpreter: every CPU test
    of the model would pay it).  Read from the platform and the shape; no
    knob."""
    return _on_tpu() and pallas_kernels.unit_lower_inverse_tiles(c)


def _inverse_slabs_loop(cols: jax.Array) -> jax.Array:
    """XLA's schedule: a ``fori_loop`` of C row steps over the whole
    [row, column, matrix] array, a step one multiply-and-sum over the
    finished slabs and one slab written."""
    c = cols.shape[0]
    eye = jnp.eye(c, dtype=cols.dtype)

    def row(i, t, done):                # t [k, j, m]: rows < i are final
        a_i = lax.dynamic_index_in_dim(cols, i, 0, keepdims=False)
        e_i = lax.dynamic_index_in_dim(eye, i, 0, keepdims=False)
        new = e_i[:, None] - (a_i[:done, None, :] * t[:done]).sum(0)
        return lax.dynamic_update_index_in_dim(t, new, i, 0)

    # cols * 0: zeros that vary over a shard_map's axes as the rows will.
    # In stages, so that a row reads the rows up to its stage's end only.
    t, stage = cols * 0.0, min(c, 16)
    for start in range(0, c, stage):
        end = min(start + stage, c)
        t = lax.fori_loop(start, end, functools.partial(row, done=end), t)
    return t


@jax.custom_vjp
def _unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I + a)^-1`` for strictly lower triangular ``a`` [..., C, C] in
    float32, by forward substitution, row by row: row i of the inverse is
    ``e_i - sum_k a[i, k] (row k)`` over the rows before it (the later
    ones are still 0, and ``a[i, k]`` is 0 there anyway).  Exact where a
    Neumann series is not: the powers of ``a`` grow combinatorially where
    neighbouring keys are alike (a strict lower triangle of ones reaches
    1e18 by the 32nd) and cancel to nothing in float32.  The matrices are
    laid [row, column, matrix] for it, so that a row of all of them is one
    slab whose last dimension is the matrices (whole vector lanes, where a
    64-wide row fills half of them); no product goes to the MXU.

    One algorithm, two schedules of it (:func:`_inverse_on_kernel`
    chooses).  On a TPU, one Mosaic call
    (``pallas_kernels.unit_lower_inverse_slabs``, under
    ``hvdt.kernel.gdn_inverse``): a block of 128 matrices makes all C row
    steps in VMEM, ``a`` is read once and the inverse written once.
    Elsewhere, and for a C the kernel does not tile, XLA's ``fori_loop``
    in four stages (:func:`_inverse_slabs_loop`), whose every row step
    re-reads the finished rows from HBM.  On the v5e for the 8,192
    matrices of 64 x 64 of one call of ``qwen3_next_s16384`` (device
    events; my chip runs, PR 34, PERF.md section 6): the kernel 0.48 ms, at
    the bound of its 268 MB, the loop 8.04; six calls a step, 47 of its
    826 ms.

    Not differentiated: the inverse's own cotangent rule is two products
    (``_unit_lower_inverse_bwd``)."""
    c = a.shape[-1]
    cols = jnp.transpose(a.reshape((-1, c, c)), (1, 2, 0))  # [i, k, m]
    slabs = (pallas_kernels.unit_lower_inverse_slabs
             if _inverse_on_kernel(c) else _inverse_slabs_loop)
    return jnp.transpose(slabs(cols), (2, 0, 1)).reshape(a.shape)


def _unit_lower_inverse_fwd(a):
    t = _unit_lower_inverse(a)
    return t, t


def _unit_lower_inverse_bwd(t, ct):
    # d(T) = -T d(a) T, so <ct, dT> = <-T^T ct T^T, da>, on the strict
    # lower triangle a lives on.
    c = t.shape[-1]
    tt = jnp.swapaxes(t, -1, -2)
    grad = -jnp.matmul(jnp.matmul(tt, ct, precision=lax.Precision.HIGHEST),
                       tt, precision=lax.Precision.HIGHEST)
    return (jnp.where(jnp.tril(jnp.ones((c, c), bool), -1), grad, 0.0),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _l2norm(x):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _log_decay(g, n: int, chunk: int, dims, carry_dtype):
    """The log of gamma inside each chunk: the cumulative sum of the
    log-decays g [B, N C, Hv], float32 (``carry_dtype`` for the
    benchmark's control): [B, N, C, Hk, R]."""
    hk, hv = dims[0], dims[1]
    g = g.astype(jnp.float32).reshape(g.shape[0], n, chunk, hk, hv // hk)
    return jnp.cumsum(g.astype(carry_dtype), axis=2).astype(jnp.float32)


def _split_rows(qkv, n: int, chunk: int, dims):
    """The rows [B, N C, 2 Kd + Vd] as q, k [B, N, C, Hk, dk] and
    v [B, N, C, Hk, R, dv]."""
    hk, hv, dk, dv = dims
    b, kd = qkv.shape[0], hk * dk
    return (qkv[..., :kd].reshape(b, n, chunk, hk, dk),
            qkv[..., kd:2 * kd].reshape(b, n, chunk, hk, dk),
            qkv[..., 2 * kd:].reshape(b, n, chunk, hk, hv // hk, dv))


def _pairs(x, y):                       # x_i . y_j a key head: [B,N,Hk,C,C]
    return jnp.einsum("bnihd,bnjhd->bnhij", x, y,
                      preferred_element_type=jnp.float32)


def _decay_ratios(gc):
    """gamma_i / gamma_j for j <= i, 0 above the diagonal, as the exp of
    a difference: gc [B,N,C,Hk,R] -> [B,N,Hk,R,C,C]."""
    c = gc.shape[2]
    gc_rows = jnp.moveaxis(gc, 2, -1)                   # [B,N,Hk,R,C]
    diff = gc_rows[..., :, None] - gc_rows[..., None, :]
    lower = jnp.tril(jnp.ones((c, c), bool))
    return jnp.exp(jnp.where(lower, diff, -jnp.inf))


def _chunk_factors(qkv, gc, beta, dims, chunk):
    """What both directions of the rule derive from its inputs: v, the
    float32 rows of q and k and their unit rows, the normed q and k in the
    compute dtype, ``K K^T`` and ``Q K^T`` a key head, gamma, the decay
    ratios, beta a row, and the normed k a value head."""
    dt = qkv.dtype
    q, k, v = _split_rows(qkv, qkv.shape[1] // chunk, chunk, dims)
    qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)
    qy, ky = _l2norm(qf), _l2norm(kf)
    qn, kn = (qy * dims[2] ** -0.5).astype(dt), ky.astype(dt)
    return (v, qf, kf, qy, ky, qn, kn,
            _pairs(kn, kn)[:, :, :, None],              # [B,N,Hk,1,C,C]
            _pairs(qn, kn)[:, :, :, None],
            jnp.exp(gc), _decay_ratios(gc),
            jnp.moveaxis(beta, 2, -1)[..., None],       # [B,N,Hk,R,C,1]
            kn[:, :, :, :, None])                       # [B,N,C,Hk,1,dk]


def _chunk_fwd_jax(qkv, gc, beta, dims, chunk):
    """XLA's schedule of the chunk-local passes: every product a batched
    einsum over (batch, chunk, head), every pass between them XLA's to
    fuse.  Returns the outputs of :func:`_chunk_passes` and ``T``
    [B,N,Hk,R,C,C] in float32 for its backward."""
    f32, dt = jnp.float32, qkv.dtype
    (v, _, _, _, _, qn, _, kk, qk, gamma, ratio, beta_rows,
     kv) = _chunk_factors(qkv, gc, beta, dims, chunk)
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    t = _unit_lower_inverse(jnp.where(strict, beta_rows * ratio * kk, 0.0))
    attn = (ratio * qk).astype(dt)

    def rows(m, x):                     # m [B,N,Hk,R,C,C] @ x [B,N,C,Hk,R,D]
        return jnp.einsum("bnhrij,bnjhrd->bnhrid", m, x,
                          preferred_element_type=f32)

    tb = t.astype(dt)
    u_own = rows(tb, (beta[..., None] * v).astype(dt))  # T (beta V)
    w = rows(tb, ((beta * gamma)[..., None] * kv).astype(dt)).astype(dt)
    # (gamma_C / gamma) K
    k_out = (jnp.exp(gc[:, :, -1:] - gc)[..., None] * kv).astype(dt)
    return (qn, w, u_own, jnp.moveaxis(k_out, 2, 4), attn), t


def _l2norm_bwd(x, y, ct):
    """The cotangent of ``x`` under ``y = x rsqrt(sum x^2 + eps)``, all
    float32: r (ct - y (y . ct))."""
    r = lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    return r * (ct - y * jnp.sum(y * ct, -1, keepdims=True))


def _chunk_bwd_jax(qkv, gc, beta, t, cts, dims, chunk):
    """XLA's schedule of the backward rule: the cotangents of (qkv, gc,
    beta) from those of the five outputs.  Residuals are the inputs, the
    2 MB of ``gc`` and ``T``; the norms, the ratios, ``A``'s factors and
    the casts are derived again here.  Products take operands in the
    compute dtype and accumulate in float32; the inverse's cotangent is
    ``-T^T ct T^T`` on the strict lower triangle, in float32 at
    ``HIGHEST``; every sum over a chunk's tokens is float32."""
    f32, dt = jnp.float32, qkv.dtype
    ct_qn, ct_w, ct_u, ct_kout, ct_attn = cts
    ct_kout = jnp.moveaxis(ct_kout, 4, 2).astype(f32)
    (v, qf, kf, qy, ky, qn, kn, kk, qk, gamma, ratio, beta_rows,
     kv) = _chunk_factors(qkv, gc, beta, dims, chunk)
    bv = (beta[..., None] * v).astype(dt)
    bg = beta * gamma
    bgk = (bg[..., None] * kv).astype(dt)
    tb = t.astype(dt)

    def outer(x, y):                    # x_i . y_j a value head
        return jnp.einsum("bnhrid,bnjhrd->bnhrij", x, y,
                          preferred_element_type=f32)

    def rows_t(m, x):                   # m^T x: [B,N,C,Hk,R,D]
        return jnp.einsum("bnhrij,bnhrid->bnjhrd", m, x,
                          preferred_element_type=f32)

    ct_ub = ct_u.astype(dt)
    d_t = outer(ct_ub, bv) + outer(ct_w, bgk)
    d_bv, d_bgk = rows_t(tb, ct_ub), rows_t(tb, ct_w)
    d_a, = _unit_lower_inverse_bwd(t, d_t)
    # A = beta_i ratio_ij (k_i . k_j);  attn = ratio_ij (q_i . k_j)
    d_a_ratio = d_a * ratio
    ct_attn_ratio = ct_attn.astype(f32) * ratio
    d_kk = (d_a_ratio * beta_rows).sum(3).astype(dt)    # [B,N,Hk,C,C]
    d_qk = ct_attn_ratio.sum(3).astype(dt)
    d_beta_rows = (d_a_ratio * kk).sum(-1)              # [B,N,Hk,R,C]
    # ratio = exp(gc_i - gc_j): what reaches gc through it
    m = d_a_ratio * beta_rows * kk + ct_attn_ratio * qk
    d_gc_rows = m.sum(-1) - m.sum(-2)                   # [B,N,Hk,R,C]
    # K_out = exp(gc_C - gc) K;  W's operand beta gamma K;  gamma itself
    e = jnp.exp(gc[:, :, -1:] - gc)                     # [B,N,C,Hk,R]
    d_e = (ct_kout * kv).sum(-1) * e
    d_bg = (d_bgk * kv).sum(-1)
    d_gc = jnp.moveaxis(d_gc_rows, -1, 2) + beta * d_bg * gamma - d_e
    d_gc = d_gc.at[:, :, -1].add(d_e.sum(2))
    d_beta = (jnp.moveaxis(d_beta_rows, -1, 2) + (d_bv * v).sum(-1)
              + gamma * d_bg)
    # the normed rows, then the rows themselves
    d_kn = (jnp.einsum("bnhij,bnjhd->bnihd", d_kk, kn,
                       preferred_element_type=f32)
            + jnp.einsum("bnhij,bnihd->bnjhd", d_kk, kn,
                         preferred_element_type=f32)
            + jnp.einsum("bnhij,bnihd->bnjhd", d_qk, qn,
                         preferred_element_type=f32)
            + (d_bgk * bg[..., None]).sum(4)
            + (ct_kout * e[..., None]).sum(4))
    d_qn = ct_qn.astype(f32) + jnp.einsum(
        "bnhij,bnjhd->bnihd", d_qk, kn, preferred_element_type=f32)
    d_q = _l2norm_bwd(qf, qy, d_qn * dims[2] ** -0.5).astype(dt)
    d_k = _l2norm_bwd(kf, ky, d_kn).astype(dt)
    d_v = (d_bv * beta[..., None]).astype(dt)
    flat = lambda x: x.reshape(qkv.shape[:2] + (-1,))   # noqa: E731
    return (jnp.concatenate([flat(d_q), flat(d_k), flat(d_v)], -1), d_gc,
            d_beta)


def _chunk_on_kernels(qkv, dims, chunk: int) -> bool:
    """Which schedule of the chunk-local passes and of their rule runs: on
    a TPU, for rows the kernels tile (``pallas_kernels.gdn_chunk_tiles``:
    chunks of 64, heads of whole lane tiles), three Mosaic calls around
    PR 34's solve; elsewhere XLA's.  Read from the platform and the shapes,
    as :func:`_inverse_on_kernel`; no knob."""
    return _on_tpu() and pallas_kernels.gdn_chunk_tiles(qkv.shape[1], dims,
                                                        chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _chunk_passes(qkv, gc, beta, dims, chunk):
    """Everything :func:`gated_delta_rows` computes for all the chunks at
    once, before the state's loop, as one function with a differentiation
    rule of its own: from the rows ``qkv`` [B, N C, 2 Kd + Vd] (columns
    [q | k | v], the compute dtype), the chunks' cumulative log-decays
    ``gc`` and ``beta`` [B, N, C, Hk, R] (float32) to

        qn    [B,N,C,Hk,dk]     q, L2-normed a head, / sqrt(dk)
        w     [B,N,Hk,R,C,dk]   T (beta gamma K)
        u_own [B,N,Hk,R,C,dv]   T (beta V), float32
        k_out [B,N,Hk,R,C,dk]   (gamma_C / gamma) K
        attn  [B,N,Hk,R,C,C]    tril((gamma_i / gamma_j) (q_i . k_j))

    (``dims`` = (Hk, Hv, dk, dv); the compute dtype where none is named;
    ``qn`` on q's own rows, the others head-major, a chunk's [C, d] or
    [C, C] matrix of a head whole tiles).

    The rule is written by hand (:func:`_chunk_bwd_jax` states it): its
    residuals are the three inputs and ``T`` (float32, laid as the
    schedule that made it leaves it), not the two dozen [C, C]
    and [C, d] arrays between them that differentiating the passes one by
    one would keep and transpose.  One rule, two schedules of it
    (:func:`_chunk_on_kernels` chooses): XLA's (:func:`_chunk_fwd_jax`,
    :func:`_chunk_bwd_jax`) and Mosaic's (``pallas_kernels.
    gdn_chunk_forward`` / ``gdn_chunk_backward``: a program holds a block
    of chunks of one key head in VMEM, reads q, k, v from the rows' column
    blocks once and writes each output once).  On the v5e for one layer of
    ``qwen3_next_s16384`` (device events; my chip runs, PR 43, PERF.md
    section 6): Mosaic's schedule 4.7 ms forward and 7.3 backward, XLA's
    13.1 and 28.6 (and 3.4% more of the step's memory)."""
    return _chunk_passes_fwd(qkv, gc, beta, dims, chunk)[0]


def _chunk_passes_fwd(qkv, gc, beta, dims, chunk):
    if _chunk_on_kernels(qkv, dims, chunk):
        out, t = pallas_kernels.gdn_chunk_forward(qkv, gc, beta, dims, chunk)
    else:
        out, t = _chunk_fwd_jax(qkv, gc, beta, dims, chunk)
    return out, (qkv, gc, beta, t)


def _chunk_passes_bwd(dims, chunk, res, cts):
    qkv = res[0]
    bwd = (pallas_kernels.gdn_chunk_backward
           if _chunk_on_kernels(qkv, dims, chunk) else _chunk_bwd_jax)
    # JAX names the rule's equations after the forward's call
    # (transpose(jvp(.. hvdt.gdn.scan.chunk))): a scope opened here would
    # nest the same name inside itself.
    return bwd(*res, cts, dims, chunk)


_chunk_passes.defvjp(_chunk_passes_fwd, _chunk_passes_bwd)


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array,
                     g: jax.Array, beta: jax.Array, *,
                     chunk: int = CHUNK,
                     carry_dtype=jnp.float32) -> jax.Array:
    """The gated delta rule over whole sequences, in chunks.

    q, k: [B, L, Hk, dk] (L2-normalised a head here, q also divided by
    sqrt(dk)); v: [B, L, Hv, dv] with ``Hk`` dividing ``Hv`` (value head n
    reads key head ``n // (Hv / Hk)``); g (the log of the decay, <= 0)
    and beta: [B, L, Hv] float32.  Returns o [B, L, Hv, dv] in float32.
    A length that is not whole chunks is padded at its end with tokens
    that write nothing (k = v = beta = g = 0) and whose outputs are
    dropped.  ``carry_dtype`` is the dtype of what is carried along the
    sequence, the cumulative sum of the log-decays inside a chunk and the
    state from chunk to chunk: float32; the benchmark's control passes
    bfloat16 to show what its reference check tells apart."""
    b, l = q.shape[:2]
    dims = (q.shape[2], v.shape[2], q.shape[3], v.shape[3])
    with jax.named_scope("hvdt.gdn.scan.chunk"):
        qkv = jnp.concatenate([q.reshape(b, l, -1), k.reshape(b, l, -1),
                               v.reshape(b, l, -1)], -1)
    return gated_delta_rows(qkv, g, beta, dims, chunk=chunk,
                            carry_dtype=carry_dtype)


def gated_delta_rows(qkv: jax.Array, g: jax.Array, beta: jax.Array,
                     dims, *, chunk: int = CHUNK,
                     carry_dtype=jnp.float32) -> jax.Array:
    """:func:`gated_delta_rule` on the projection's own rows: ``qkv``
    [B, L, 2 Kd + Vd] with the columns [q | k | v], a head a column block,
    ``dims`` = (Hk, Hv, dk, dv).  The mixer calls this, so that no copy of
    q, k or v is made in front of the chunk-local passes."""
    hk, hv, dk, dv = dims
    b, l, _ = qkv.shape
    r = hv // hk
    dt = qkv.dtype
    f32 = jnp.float32
    pad = (-l) % chunk
    n = (l + pad) // chunk

    def padded(x):
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

    def step(s, xs):                    # s [B, Hk, R, dk, dv], carry_dtype
        w_n, u_n, k_n, g_n = xs
        u = (u_n - jnp.einsum("bhrid,bhrde->bhrie", w_n, s.astype(dt),
                              preferred_element_type=f32)).astype(dt)
        s_next = (g_n[..., None, None] * s.astype(f32)
                  + jnp.einsum("bihrd,bhrie->bhrde", k_n, u,
                               preferred_element_type=f32))
        return s_next.astype(carry_dtype), (s.astype(dt), u)

    # Three parts under the caller's hvdt.gdn.scan, one reader of the
    # benchmark each: what is batched over the chunks, the sequential
    # loop over them, and O from both.
    with jax.named_scope("hvdt.gdn.scan.chunk"):
        gc = _log_decay(padded(g), n, chunk, dims, carry_dtype)
        beta = padded(beta.astype(f32)).reshape(b, n, chunk, hk, r)
        qn, w, u_own, k_out, attn = _chunk_passes(padded(qkv), gc, beta,
                                                  dims, chunk)
        # as the state's loop and O name them
        k_out = jnp.moveaxis(k_out, 4, 2)
        gamma = jnp.exp(gc)
        gamma_end = gamma[:, :, -1]                     # [B, N, Hk, R]
        first = lambda x: jnp.moveaxis(x, 1, 0)         # noqa: E731
        xs = (first(w), first(u_own), first(k_out), first(gamma_end))
        s0 = jnp.zeros((b, hk, r, dk, dv), carry_dtype)
        # Inside a shard_map the operands are varying over its axes and
        # so is the state the body returns: the initial state has to
        # match.
        vma = tuple(set().union(*(jax.typeof(x).vma for x in xs)))
        if vma:
            s0 = lax.pcast(s0, vma, to="varying")
    with jax.named_scope("hvdt.gdn.scan.state"):
        _, (s_in, u) = lax.scan(step, s0, xs)
    with jax.named_scope("hvdt.gdn.scan.out"):
        s_in, u = jnp.moveaxis(s_in, 0, 1), jnp.moveaxis(u, 0, 1)
        q_in = (gamma[..., None] * qn[:, :, :, :, None]).astype(dt)
        # The first product leaves in the compute dtype (the MXU
        # accumulates in float32 all the same; the CPU's runtime has no
        # bf16 x bf16 = f32 product in this transposed form) and is added
        # in float32.
        o = (jnp.einsum("bnihrd,bnhrde->bnihre", q_in, s_in).astype(f32)
             + jnp.einsum("bnhrij,bnhrje->bnihre", attn, u,
                          preferred_element_type=f32))
        return o.reshape(b, n * chunk, hv, dv)[:, :l]


def gated_rmsnorm(o: jax.Array, z: jax.Array, w: jax.Array, *,
                  eps: float = 1e-6, gate_first: bool = False) -> jax.Array:
    """``RMS(o) * w * silu(z)`` over the last dimension (a head, or a
    group of channels), in float32; with ``gate_first`` the gate is inside
    the norm, ``RMS(o * silu(z)) * w``."""
    o, z = o.astype(jnp.float32), z.astype(jnp.float32)
    if gate_first:
        o = o * jax.nn.silu(z)
    o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
    o = o * w.astype(jnp.float32)
    return o if gate_first else o * jax.nn.silu(z)


def gated_delta_net(x: jax.Array, p: Dict[str, jax.Array], *,
                    key_heads: int, value_heads: int, key_dim: int,
                    value_dim: int,
                    proj: Callable[[jax.Array, jax.Array], jax.Array]
                    ) -> jax.Array:
    """The Gated DeltaNet mixer on x [B, L, d] (already normed).

    ``p``: ``w_qkvz`` [d, 2 Kd + 2 Vd] with the columns [q | k | v | z]
    (Kd = key_heads x key_dim, Vd = value_heads x value_dim), ``w_ba`` [d,
    2 value_heads] with the columns [b | a], ``conv`` [taps, 2 Kd + Vd]
    over the channels [q | k | v], ``a_log`` and ``dt_bias``
    [value_heads], ``gdn_norm`` [value_dim], ``w_out`` [Vd, d].  ``proj``
    is the model's dense projection (``x @ w`` in the compute dtype)."""
    b, l, _ = x.shape
    kd, vd = key_heads * key_dim, value_heads * value_dim
    f32 = jnp.float32
    with jax.named_scope("hvdt.gdn.proj"):
        # Two products on the matrix's column blocks, so that no [q|k|v|z]
        # row exists: the convolution's and the gate's cotangents would
        # each be padded to its width, in float32.
        qkv = proj(x, p["w_qkvz"][:, :2 * kd + vd])
        z = proj(x, p["w_qkvz"][:, 2 * kd + vd:])
        # b and a feed a sigmoid and an exponent's argument: float32 out
        ba = x.astype(f32) @ p["w_ba"].astype(f32)
    with jax.named_scope("hvdt.gdn.conv"):
        qkv = jax.nn.silu(causal_conv(qkv, p["conv"]))
    with jax.named_scope("hvdt.gdn.scan"):
        # with the rule's own work before its loop, so that the three
        # children account for all of hvdt.gdn.scan
        with jax.named_scope("hvdt.gdn.scan.chunk"):
            beta = jax.nn.sigmoid(ba[..., :value_heads])
            g = -jnp.exp(p["a_log"].astype(f32)) * jax.nn.softplus(
                ba[..., value_heads:] + p["dt_bias"].astype(f32))
        o = gated_delta_rows(qkv, g, beta, (key_heads, value_heads, key_dim,
                                            value_dim))
    with jax.named_scope("hvdt.gdn.norm"):
        y = gated_rmsnorm(o, z.reshape(b, l, value_heads, value_dim),
                          p["gdn_norm"]).astype(x.dtype)
    with jax.named_scope("hvdt.gdn.proj"):
        return proj(y.reshape(b, l, vd), p["w_out"])


def scan_macs_per_token(*, key_heads: int, value_heads: int, key_dim: int,
                        value_dim: int, chunk: int = CHUNK) -> float:
    """Forward multiply-adds a token of :func:`gated_delta_rule`: a key
    head's two pair products (k.k, q.k), a value head's three chunk
    products (T beta V, T beta gamma K, the pairs times U), its three
    products with the state (W S_0, Q S_0, K^T U) and the inverse's
    C^3 / 3, over the chunk's tokens."""
    c = chunk
    return (key_heads * 2 * c * c * key_dim
            + value_heads * (c * c * (key_dim + 2 * value_dim)
                             + 3 * c * key_dim * value_dim
                             + c ** 3 / 3)) / c
