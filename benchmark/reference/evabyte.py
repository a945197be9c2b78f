"""Plain reference for the ``evabyte`` family: a byte-level decoder LM whose
attention is EVA (Zheng et al., arXiv:2302.04542, as EvaByte, 6.5B, runs
it) with several prediction heads a row, written from the equations in
straightforward ``jax.numpy``: float32 throughout,
``jax.default_matmul_precision("highest")``, token-major, both boolean
masks built dense from their definitions, the scores over ``[keys ;
summaries]`` materialised and put through ONE softmax, no kernels.  It
imports nothing from ``horovod_tpu``.  It reads the configuration file's
own keys (the source's ``config.json`` names) and the parameter pytree the
system trains, so gradients compare leaf by leaf:

    params["period"]["0"]      the layers, leaves stacked [layers, 1, ...]
    params["embed"]            [320, hidden];  params["ln_f"]
    params["head"]             [num_pred_heads x 320, hidden], head n's
                               rows after head n - 1's

The layer (x [L, 4096]; N(x; w) = x / sqrt(mean(x^2) + rms_norm_eps) * (1 +
w), ``norm_add_unit_offset``; no biases):

    h = N(x; ln1);  q, k, v = h Wq, h Wk, h Wv   [L, heads held, 128]
    q, k <- RoPE(q), RoPE(k)    all 128 dimensions of a head, rotate-half,
                                position * theta^(-2i/128), theta 1e5
    summaries, a head with its learned phi, mu in R^128: the sequence in
      chunks of chunk_size = 16 consecutive bytes; for chunk c over its keys
      a_cj = softmax_j(k_j . phi);  k~_c = sum_j a_cj k_j + mu;
      v~_c = sum_j a_cj v_j
    aggregation, W = window_size = 2048: query i sees the exact keys j with
      j // W == i // W and j <= i, and the summaries c with (16 c) // W <
      i // W (every chunk of every EARLIER window; none of its own);
      o_i = softmax([q_i . k_j ; q_i . k~_c] / sqrt(128)) [v_j ; v~_c]
    x <- x + concat(o) Wo
    h = N(x; ln2);  x <- x + (silu(h W_gate) * h W_up) W_down

The head and the loss: logits_i = N(x_i; ln_f) W_head^T in R^(8 x 320);
head n of row i is scored against byte x_{i+1+n}; the loss is the mean of
-log softmax(logits_i[n])[x_{i+1+n}] over every pair (i, n) with i + 1 + n <
L, the heads weighted alike.

Departures from the published description, each an entry of the
configuration file's ``assumed``: the pooling's form (the softmax of k .
phi after RoPE with no scale, mu added to the pooled key), the head as one
matrix with the equal-weight loss; and THE SHARE: only heads ``heads_first
.. heads_first + heads - 1`` of the 32 are held, so the attention sublayer
adds their part of concat(o) Wo alone (what the absent heads would add is
left out, here as in the system) and that partial sum goes on to the
feed-forward, which is whole.

Memory is rescheduled and no operation or its order is changed: each layer
is under ``jax.checkpoint``, and inside it so are the attention sublayer
as a whole, its scores in blocks of ``QUERY_BLOCK`` query rows (8 heads x
32,768 x 34,816 x 4 B is 36 GB whole), each sublayer's norm with its
products with the weights in blocks of ``ROW_BLOCK`` rows (a norm is a
row's own) and the logits in blocks of ``LOGIT_BLOCK`` rows: a float32
[32,768, 4,096] activation is 0.5 GiB, and the check runs beside three
trees of 2.31 GiB.  The layers are scanned over their stacked leaves,
which is the loop over them; a batch's sequences are a Python loop.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# Memory only (the check runs beside the weights and two gradient trees in
# 16 GB).
QUERY_BLOCK = 128
ROW_BLOCK = 2048
LOGIT_BLOCK = 2048


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def blocks_of(length: int, most: int) -> int:
    """The largest divisor of ``length`` that is at most ``most``."""
    return next(b for b in range(min(most, length), 0, -1)
                if length % b == 0)


def rotate(x, theta: float):
    """x [L, H, D], row i at position i: every dimension of a head rotates
    by position * theta^(-2i / D), pairs (i, i + D / 2)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def summaries(k, v, phi, mu, chunk: int):
    """k, v [L, H, D] -> (k~, v~) [L / chunk, H, D]."""
    length, heads, dh = k.shape
    kc = k.reshape(length // chunk, chunk, heads, dh)
    vc = v.reshape(length // chunk, chunk, heads, dh)
    a = jax.nn.softmax(jnp.einsum("cjhd,hd->cjh", kc, phi), axis=1)
    return (jnp.einsum("cjh,cjhd->chd", a, kc) + mu,
            jnp.einsum("cjh,cjhd->chd", a, vc))


def visible(rows, length: int, window: int, chunk: int):
    """[rows, length + length / chunk] bool: whether query ``rows[i]`` sees
    exact key j (the first ``length`` columns) or summary c (the rest)."""
    i = rows[:, None]
    j = jnp.arange(length)[None, :]
    c = jnp.arange(length // chunk)[None, :]
    exact = (j // window == i // window) & (j <= i)
    summary = (chunk * c) // window < i // window
    return jnp.concatenate([exact, summary], axis=1)


def attention(x, p, config: dict):
    """x [L, hidden] -> concat(o) Wo of the heads held, from h = N(x; ln1)
    (the norm and the three projections a block of rows at a time)."""
    length = x.shape[0]
    dh = config["hidden_size"] // config["num_attention_heads"]
    heads = p["wq"].shape[1] // dh
    window, chunk = config["window_size"], config["chunk_size"]
    rows_block = blocks_of(length, ROW_BLOCK)

    @jax.checkpoint
    def project(rows):
        h = norm(rows, p["ln1"], config["rms_norm_eps"])
        return h @ p["wq"], h @ p["wk"], h @ p["wv"]

    q, k, v = (a.reshape(length, heads, dh) for a in jax.lax.map(
        project, x.reshape(-1, rows_block, x.shape[1])))
    q = rotate(q, config["rope_theta"])
    k = rotate(k, config["rope_theta"])
    ks, vs = summaries(k, v, p["phi"], p["mu"], chunk)
    block = blocks_of(length, QUERY_BLOCK)

    @jax.checkpoint
    def some(args):
        q_rows, rows = args                     # [block, H, D], [block]
        # the scores over [keys ; summaries], through one softmax
        s = jnp.concatenate([jnp.einsum("qhd,khd->hqk", q_rows, k),
                             jnp.einsum("qhd,chd->hqc", q_rows, ks)],
                            -1) / math.sqrt(dh)
        s = jnp.where(visible(rows, length, window, chunk), s, -jnp.inf)
        w = jax.nn.softmax(s, -1)
        return (jnp.einsum("hqk,khd->qhd", w[..., :length], v)
                + jnp.einsum("hqc,chd->qhd", w[..., length:], vs))

    o = jax.lax.map(some, (q.reshape(-1, block, heads, dh),
                           jnp.arange(length).reshape(-1, block)))
    return o.reshape(length, heads * dh) @ p["wo"]


def feed_forward(x, p, eps):
    """(silu(h W_gate) * h W_up) W_down of h = N(x; ln2), a block of rows
    at a time (the norm is a row's own, so it is taken inside the block)."""
    block = blocks_of(x.shape[0], ROW_BLOCK)

    @jax.checkpoint
    def some(rows):
        h = norm(rows, p["ln2"], eps)
        return (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) \
            @ p["w_down"]

    return jax.lax.map(some, x.reshape(-1, block, x.shape[1])).reshape(
        x.shape)


def layer(x, p, config: dict):
    x = x + jax.checkpoint(lambda x: attention(x, p, config))(x)
    return x + feed_forward(x, p, config["rms_norm_eps"])


def sequence_nll(params, ids, config: dict):
    """One sequence ``ids`` [L]: the sum of -log p over every (row, head)
    pair that has a target."""
    length, n = ids.shape[0], config["num_pred_heads"]
    x = params["embed"][ids]
    # The period is one layer: the scan runs over the leaves as they are
    # stacked, [layers, 1, ...], and a layer reads its own as [...] by a
    # reshape, so that the gradients are written into the stacked leaves in
    # place (a slice inside the loop, or a view [layers, ...] outside it,
    # keeps a second copy of every layer's gradient as a temporary).
    one = jax.checkpoint(lambda x, p: layer(
        x, jax.tree.map(lambda a: a.reshape(a.shape[1:]), p), config))
    x, _ = jax.lax.scan(lambda x, p: (one(x, p), None), x,
                        params["period"]["0"])
    x = norm(x, params["ln_f"], config["rms_norm_eps"])
    ahead = jnp.pad(ids, (0, n))
    # targets[i, m] = x_{i+1+m}; pairs past the sequence's end do not count.
    targets = jnp.stack([ahead[1 + m:1 + m + length] for m in range(n)], -1)
    counts = jnp.arange(length)[:, None] + jnp.arange(n) + 1 < length
    block = blocks_of(length, LOGIT_BLOCK)

    @jax.checkpoint
    def some(args):
        x_rows, t_rows, c_rows = args
        logp = jax.nn.log_softmax(
            (x_rows @ params["head"].T).reshape(block, n, -1), -1)
        ll = jnp.take_along_axis(logp, t_rows[..., None], -1)[..., 0]
        return -jnp.where(c_rows, ll, 0.0).sum()

    return jax.lax.map(some, (x.reshape(-1, block, x.shape[1]),
                              targets.reshape(-1, block, n),
                              counts.reshape(-1, block, n))).sum()


def loss(params, tokens, *, config: dict):
    """The multi-byte prediction loss of ``tokens`` [B, L]."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        batch, length = tokens.shape
        pairs = sum(max(length - 1 - m, 0)
                    for m in range(config["num_pred_heads"]))
        return sum(sequence_nll(params, ids, config)
                   for ids in tokens) / (batch * pairs)
