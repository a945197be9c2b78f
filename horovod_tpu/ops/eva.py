"""EVA attention (Zheng et al., arXiv:2302.04542, as EvaByte runs it): exact
softmax attention inside aligned windows of ``window`` positions, joined in
ONE softmax with a learned summary of every ``chunk`` positions of every
earlier window.

A head, with its two learned vectors ``phi`` and ``mu`` (float32 [D]):

* **Summaries** (:func:`eva_summaries`).  The sequence is cut into chunks of
  ``chunk`` consecutive positions.  Chunk c pools its (rotated) keys by a
  softmax of their products with ``phi``: ``a_cj = softmax_j(k_j . phi)``,
  ``k~_c = sum_j a_cj k_j + mu``, ``v~_c = sum_j a_cj v_j``.  Float32.
* **Aggregation** (:func:`eva_attention`).  Query i, in window ``w(i) = i //
  window``, sees the exact keys ``window * w(i) <= j <= i`` of its own
  window and the summaries ``c < (window / chunk) * w(i)`` of every chunk
  of every earlier window, and ``o_i = softmax([q_i . k_j ; q_i . k~_c] /
  sqrt(D)) [v_j ; v~_c]``: one softmax over both sets, in float32.  The
  first window is plain causal attention; a chunk of a row's own window is
  never a summary.

Two forms of the aggregation, chosen by :func:`ops.attention.kernel_plan`
on the windows' shape, as every other mask's kernel is:

* the kernels.  The aligned windows are L / window independent causal
  problems: the exact part is the causal flash kernels on ``[B x L /
  window, window]`` rows (``pallas_kernels.flash_attention_stats``: out AND
  logsumexp, both differentiable, under ``hvdt.kernel.eva_win_*``); the
  summaries are a second, small attention under the mask "the chunk's
  window is before the row's" (``pallas_kernels.eva_summary_attention``
  where its tiles fit, else the XLA form of the same, a window at a time);
  the two are joined by their logsumexps, which is the one softmax;
* the blocked XLA form (:func:`_eva_xla`): a window at a time, the scores
  over ``[keys ; summaries]`` materialised for that window.  The CPU path,
  and the path under ``_CROSSOVER_SEQ`` and where no kernel can be called.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import pallas_kernels
from .attention import attention, kernel_plan

__all__ = ["eva_attention", "eva_summaries", "eva_visible_pairs"]

_MASKED = -1e30


def eva_visible_pairs(seq: int, window: int, chunk: int):
    """(exact, summary) query-key pairs a head of one sequence sees."""
    if seq <= window:
        return seq * (seq + 1) // 2, 0
    windows, per = seq // window, window // chunk
    return (windows * window * (window + 1) // 2,
            window * per * (windows * (windows - 1) // 2))


def eva_summaries(k, v, phi, mu, chunk: int):
    """``(k~, v~)`` [B, L / chunk, H, D] in k's dtype from the rotated keys
    and the values [B, L, H, D] and a layer's ``phi``, ``mu`` [H, D]: the
    pooling's softmax, its sums and ``mu`` in float32."""
    b, l, h, d = k.shape
    with jax.named_scope("hvdt.eva.summary"):
        kc = k.reshape(b, l // chunk, chunk, h, d).astype(jnp.float32)
        vc = v.reshape(b, l // chunk, chunk, h, d).astype(jnp.float32)
        a = jax.nn.softmax(
            jnp.einsum("bnchd,hd->bnch", kc, phi.astype(jnp.float32)), axis=2)
        ks = jnp.einsum("bnch,bnchd->bnhd", a, kc) + mu.astype(jnp.float32)
        vs = jnp.einsum("bnch,bnchd->bnhd", a, vc)
        return ks.astype(k.dtype), vs.astype(v.dtype)


def _eva_xla(q, k, v, ks, vs, window: int, chunk: int):
    """The aggregation a window at a time: that window's scores over its
    own keys and all the summaries exist, float32 out of the operands'
    dtype, under the two masks; one softmax over both."""
    b, l, h, d = q.shape
    windows, per = l // window, window // chunk
    scale = d ** -0.5
    causal = jnp.tril(jnp.ones((window, window), bool))
    chunk_window = jnp.arange(ks.shape[1]) // per

    @jax.checkpoint
    def one(args):
        w, q_w, k_w, v_w = args                 # [B, window, H, D]
        exact = jnp.einsum("bqhd,bkhd->bhqk", q_w, k_w,
                           preferred_element_type=jnp.float32) * scale
        summary = jnp.einsum("bqhd,bchd->bhqc", q_w, ks,
                             preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.concatenate(
            [jnp.where(causal, exact, _MASKED),
             jnp.where(chunk_window < w, summary, _MASKED)], -1), -1)
        p = p.astype(v.dtype)
        return (jnp.einsum("bhqk,bkhd->bqhd", p[..., :window], v_w)
                + jnp.einsum("bhqc,bchd->bqhd", p[..., window:], vs))

    def by_window(x):
        return x.reshape(b, windows, window, h, d).swapaxes(0, 1)

    o = jax.lax.map(one, (jnp.arange(windows), *map(by_window, (q, k, v))))
    return o.swapaxes(0, 1).reshape(b, l, h, d)


def _summary_attention_xla(q, ks, vs, window: int, per: int):
    """Softmax attention of every row over the summaries of the windows
    before its own, and its logsumexp: ``(out [B, L, H, D] in q's dtype,
    lse [B, H, L] f32)``; the first window's rows get 0 and -1e30.  A
    window at a time over exactly the summaries it sees; nothing but a
    window's operands is kept for the backward."""
    b, l, h, d = q.shape
    scale = d ** -0.5

    @jax.checkpoint
    def one(q_w, ks_w, vs_w):
        s = jnp.einsum("bqhd,bchd->bhqc", q_w, ks_w,
                       preferred_element_type=jnp.float32) * scale
        m = s.max(-1, keepdims=True)
        p = jnp.exp(s - m)
        total = p.sum(-1, keepdims=True)
        o = jnp.einsum("bhqc,bchd->bqhd", (p / total).astype(vs_w.dtype),
                       vs_w)
        return o, (m + jnp.log(total))[..., 0]

    outs = [jnp.zeros((b, window, h, d), q.dtype)]
    lses = [jnp.full((b, h, window), _MASKED, jnp.float32)]
    for w in range(1, l // window):
        o, lse = one(q[:, w * window:(w + 1) * window], ks[:, :w * per],
                     vs[:, :w * per])
        outs.append(o)
        lses.append(lse)
    return jnp.concatenate(outs, 1), jnp.concatenate(lses, 2)


def _eva_kernels(q, k, v, ks, vs, window: int, chunk: int):
    """The aggregation as two flash problems joined by their logsumexps."""
    b, l, h, d = q.shape
    windows, per = l // window, window // chunk
    out_w, lse_w = pallas_kernels.flash_attention_stats(
        *(x.reshape(b * windows, window, h, d) for x in (q, k, v)))
    lse_w = lse_w.reshape(b, windows, h, window).swapaxes(1, 2).reshape(
        b, h, l)
    if pallas_kernels.eva_summary_tiles(l, window, per, d, q.dtype):
        out_s, lse_s = pallas_kernels.eva_summary_attention(
            q, ks, vs, window=window, per=per)
    else:
        out_s, lse_s = _summary_attention_xla(q, ks, vs, window, per)
    # One softmax over both sets: each part's share of the joint sum.
    lse = jnp.logaddexp(lse_w, lse_s)

    def share(part):                            # [B, H, L] -> [B, L, H, 1]
        return jnp.exp(part - lse).swapaxes(1, 2)[..., None]

    o = (out_w.reshape(b, l, h, d).astype(jnp.float32) * share(lse_w)
         + out_s.astype(jnp.float32) * share(lse_s))
    return o.astype(q.dtype)


def eva_attention(q, k, v, phi, mu, *, window: int, chunk: int):
    """EVA attention of a sequence this device holds whole.  q, k, v: ``[B,
    L, H, D]`` after RoPE (as many key heads as query heads); ``phi``,
    ``mu``: ``[H, D]``; returns ``[B, L, H, D]``.  L is whole windows of
    whole chunks, or no more than one window (plain causal attention, no
    summary: ``phi`` and ``mu`` are not read)."""
    b, l, h, d = q.shape
    if k.shape[2] != h:
        raise ValueError("EVA attention takes a key head a query head "
                         f"(got {h} and {k.shape[2]})")
    if l <= window:
        return attention(q, k, v)
    if l % window or window % chunk:
        raise ValueError(f"{l} positions are not whole windows of {window} "
                         f"in whole chunks of {chunk}")
    with jax.named_scope("hvdt.eva"):
        ks, vs = eva_summaries(k, v, phi, mu, chunk)
        with jax.named_scope("hvdt.eva.core"):
            if kernel_plan(b * (l // window), window, h, h) == "direct":
                return _eva_kernels(q, k, v, ks, vs, window, chunk)
            return _eva_xla(q, k, v, ks, vs, window, chunk)
