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
products take operands in the compute dtype and accumulate in float32.

Three parts, one scope each under ``hvdt.ssd.scan``, which together
account for all of it.  ``.chunk``: what is computed for all the chunks at
once (``delta``, the running sums, the scores, the masked products inside
the chunks, each chunk's own contribution to the state).  ``.state``: the
``lax.scan`` over the chunks that carries ``S``.  ``.out``: what the
entering states add to ``y``, and ``D x``.

:func:`mamba2_mixer` is the whole mixer as the model calls it (the input
projection, a causal depthwise convolution with a bias, the scan, the
gated norm over a group's channels, the output projection), each part
under its own scope (``hvdt.ssd.proj`` / ``.conv`` / ``.scan`` /
``.norm``; the model opens ``hvdt.ssd`` around the call and its pre-norm).
"""

from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp
from jax import lax

from .gated_delta import causal_conv, gated_rmsnorm

__all__ = ["ssd_scan", "mamba2_mixer", "scan_macs_per_token"]


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
    chunk, is float32."""
    bsz, l, h, p = x.shape
    g, s = b.shape[2], b.shape[3]
    r = h // g
    dt, f32 = x.dtype, jnp.float32
    pad = (-l) % chunk
    n = (l + pad) // chunk

    def chunks(t, *tail):
        if pad:
            t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return t.reshape((bsz, n, chunk) + tail)

    with jax.named_scope("hvdt.ssd.scan.chunk"):
        x = chunks(x, g, r, p)                          # [B,N,C,G,R,P]
        delta = chunks(delta.astype(f32), g, r)         # [B,N,C,G,R]
        b, c = chunks(b, g, s), chunks(c, g, s)         # [B,N,C,G,S]
        # l_i: the running sum of log a inside the chunk
        run = jnp.cumsum(delta * a.astype(f32).reshape(g, r), axis=2)
        scores = jnp.einsum("bnigs,bnjgs->bngij", c, b,
                            preferred_element_type=f32)  # once a group
        rows = jnp.moveaxis(run, 2, -1)                 # [B,N,G,R,C]
        lower = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.exp(jnp.where(
            lower, rows[..., :, None] - rows[..., None, :], -jnp.inf))
        pairs = (scores[:, :, :, None] * decay
                 * jnp.moveaxis(delta, 2, -1)[..., None, :]).astype(dt)
        y_own = jnp.einsum("bngrij,bnjgrp->bnigrp", pairs, x,
                           preferred_element_type=f32)
        # What the chunk itself adds to the state leaving it.
        to_end = delta * jnp.exp(run[:, :, -1:] - run)  # [B,N,C,G,R]
        s_own = jnp.einsum(
            "bnjgrp,bnjgs->bngrps",
            (x.astype(f32) * to_end[..., None]).astype(dt), b,
            preferred_element_type=f32)                 # [B,N,G,R,P,S]
        xs = (jnp.moveaxis(s_own, 1, 0),
              jnp.moveaxis(jnp.exp(run[:, :, -1]), 1, 0))
        s0 = jnp.zeros((bsz, g, r, p, s), f32)
        # Inside a shard_map the operands are varying over its axes and
        # so is the state the body returns: the initial state has to
        # match.
        vma = tuple(set().union(*(jax.typeof(t).vma for t in xs)))
        if vma:
            s0 = lax.pcast(s0, vma, to="varying")

    def step(state, chunk_of):          # state [B,G,R,P,S] float32
        own, decayed = chunk_of
        return decayed[..., None, None] * state + own, state.astype(dt)

    with jax.named_scope("hvdt.ssd.scan.state"):
        _, s_in = lax.scan(step, s0, xs)
    with jax.named_scope("hvdt.ssd.scan.out"):
        y_in = jnp.einsum("bnigs,bngrps->bnigrp", c,
                          jnp.moveaxis(s_in, 0, 1),
                          preferred_element_type=f32)
        y = y_own + jnp.exp(run)[..., None] * y_in
        return y.reshape(bsz, n * chunk, h, p)[:, :l]


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
            xs = xbc[..., :inner].reshape(bsz, l, heads, head_dim)
            b = xbc[..., inner:inner + bc].reshape(bsz, l, groups, state)
            c = xbc[..., inner + bc:].reshape(bsz, l, groups, state)
        y = ssd_scan(xs, delta, a, b, c, chunk=chunk)
        with jax.named_scope("hvdt.ssd.scan.out"):
            y = y + p["d_skip"].astype(f32)[:, None] * xs.astype(f32)
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
