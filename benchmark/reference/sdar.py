"""Plain reference for the ``sdar`` family: a decoder LM of grouped-query
attention layers with a sparse feed-forward in every layer (Qwen3-MoE's
layer, which SDAR is initialised from), trained by DIFFUSION OVER BLOCKS
(BD3-LM, arXiv:2503.09573, as SDAR, arXiv:2510.06303, adopts it), written
from the equations in straightforward ``jax.numpy``: float32 throughout,
``jax.default_matmul_precision("highest")``, the boolean mask built dense
from its definition, scores materialised, every held expert applied to
every row, no kernels.  It imports nothing from ``horovod_tpu``.  It reads
the configuration file's own keys (the source's ``config.json`` names) and
the parameter pytree the system trains, so gradients compare leaf by leaf:

    params["period"]["0"]      the layers, leaves stacked [layers, 1, ...]
    params["embed"], ["head"]  [vocab rows held, hidden]; params["ln_f"]

The layer (x [rows, 2048]; RMSNorm has eps rms_norm_eps and a gain; no
biases; a row has a position, its token's place in the sequence):

    h = RMSNorm(x)
    q = RoPE(RMSNorm_head(h Wq)) [rows, 32, 128]
    k = RoPE(RMSNorm_head(h Wk)), v = h Wv [rows, 4, 128]
    s_ij = q_i . k_j / sqrt(128) where row i sees row j; query head n reads
           kv head n // 8;  x <- x + concat(softmax(s) v) Wo
    h = RMSNorm(x);  p = softmax(h Wr) in R^128;  I = top 8 of p
    w_e = p_e / sum_{e' in I} p_e'   (norm_topk_prob)
    x <- x + sum_{e in I, e held here} w_e (silu(h W_gate,e) * h W_up,e) W_down,e

RoPE rotates all 128 dimensions of a head (rotate-half, pairs (i, i + 64))
by position * theta^(-2i/128), theta 1e6.

The objective.  A sequence x_0 of L tokens is cut into L / B blocks of B.
Block b has a noise level t_b; x_t is x_0 with the masked tokens replaced
by the mask token (the last held row of the vocabulary).  The model runs
ONCE on the 2 L rows [x_t ; x_0], both halves at positions 0 .. L-1, and
row i sees row j (``visible``) where one of three terms holds:

    i noisy, j noisy, block(i) == block(j)        block-diagonal
    i noisy, j clean, block(j) <  block(i)        strictly earlier blocks
    i clean, j clean, block(j) <= block(i)        block-causal

    loss = (1 / (batch L)) sum_b (1 / t_b) sum_{i in b, masked}
           -log softmax(RMSNorm(x_i) W_head^T)[x_0,i]      (i a noisy row)

Departures from the published description, each an entry of the
configuration file's ``assumed``: the block length, the schedule (linear,
t in [1e-3, 1)) and the 1 / t weight, no shift of the logits by one, the
mask token's id, q / k norm (``config.json`` has no key; Qwen3's layer has
it), the loss's normalisation by batch x L; and THE SHARE: only experts
``experts_first .. experts_first + experts - 1`` are held, so a layer adds
their part of the routed sum alone (what the absent experts would add is
left out, here as in the system), and the vocabulary is its first
``vocab`` rows.

Memory is rescheduled and no operation or its order is changed: each layer
is under ``jax.checkpoint``, and inside it so are the scores, in blocks of
``QUERY_BLOCK`` query rows (32 heads x 16,384^2 x 4 B is 34 GB whole), the
experts in steps of ``EXPERT_GROUP`` and the logits in blocks of
``LOGIT_BLOCK`` rows.  The layers are scanned over their stacked leaves,
which is the loop over them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# Memory only (the check runs beside the weights and two gradient trees in
# 16 GB).
QUERY_BLOCK = 128
EXPERT_GROUP = 8
LOGIT_BLOCK = 2048


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def blocks_of(length: int, most: int) -> int:
    """The largest divisor of ``length`` that is at most ``most``."""
    return next(b for b in range(min(most, length), 0, -1)
                if length % b == 0)


def visible(length: int, block: int):
    """[2 L, 2 L] bool: whether row i of [x_t ; x_0] sees row j."""
    row = jnp.arange(2 * length)
    noisy = row < length
    blk = (row % length) // block
    q_noisy, k_noisy = noisy[:, None], noisy[None, :]
    q_clean, k_clean = ~q_noisy, ~k_noisy
    qb, kb = blk[:, None], blk[None, :]
    return ((q_noisy & k_noisy & (qb == kb))
            | (q_noisy & k_clean & (kb < qb))
            | (q_clean & k_clean & (kb <= qb)))


def rotate(x, positions, theta: float):
    """x [rows, H, D]: every dimension of a head rotates by position *
    theta^(-2i / D), pairs (i, i + D / 2)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(h, p, positions, seen, config: dict):
    """h [rows, hidden], seen [rows, rows] -> concat(o) Wo."""
    rows = h.shape[0]
    dh, kv = config["head_dim"], config["num_key_value_heads"]
    heads, eps = config["num_attention_heads"], config["rms_norm_eps"]
    theta = config["rope_theta"]
    q = rotate(rmsnorm((h @ p["wq"]).reshape(rows, heads, dh), p["q_norm"],
                       eps), positions, theta)
    k = rotate(rmsnorm((h @ p["wk"]).reshape(rows, kv, dh), p["k_norm"],
                       eps), positions, theta)
    v = (h @ p["wv"]).reshape(rows, kv, dh)
    group = heads // kv                 # query head n reads kv head n // group
    block = blocks_of(rows, QUERY_BLOCK)

    @jax.checkpoint
    def some(args):
        q_rows, seen_rows = args        # [block, kv, group, D], [block, rows]
        s = jnp.einsum("qngd,knd->ngqk", q_rows, k) / math.sqrt(dh)
        s = jnp.where(seen_rows, s, -jnp.inf)
        return jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(some, (q.reshape(-1, block, kv, group, dh),
                           seen.reshape(-1, block, rows)))
    return o.reshape(rows, heads * dh) @ p["wo"]


def sparse(h, p, *, per_token: int, first: int, normalise: bool):
    """The held experts' part of the routed sum (no shared expert)."""
    scores = jax.nn.softmax(h @ p["w_router"], -1)              # [rows, E]
    weights, experts = jax.lax.top_k(scores, per_token)
    if normalise:
        weights = weights / weights.sum(-1, keepdims=True)      # [rows, k]

    @jax.checkpoint
    def some(acc, group):
        e, w_gate, w_up, w_down = group             # EXPERT_GROUP experts
        w = jnp.where(experts[None] == first + e[:, None, None], weights,
                      0.0).sum(-1)                  # [experts, rows]
        mid = (jax.nn.silu(jnp.einsum("ld,edf->elf", h, w_gate))
               * jnp.einsum("ld,edf->elf", h, w_up))
        return acc + jnp.einsum("elf,efd,el->ld", mid, w_down, w), None

    held = p["w_up"].shape[0]
    size = math.gcd(held, EXPERT_GROUP)
    routed, _ = jax.lax.scan(some, jnp.zeros_like(h), jax.tree.map(
        lambda a: a.reshape((held // size, size) + a.shape[1:]),
        (jnp.arange(held), p["w_gate"], p["w_up"], p["w_down"])))
    return routed


def layer(x, p, positions, seen, config: dict):
    eps = config["rms_norm_eps"]
    x = x + attention(rmsnorm(x, p["ln1"], eps), p, positions, seen, config)
    return x + sparse(rmsnorm(x, p["ln2"], eps), p,
                      per_token=config["num_experts_per_tok"],
                      first=config["experts_first"],
                      normalise=config["norm_topk_prob"])


def hidden(params, ids, positions, seen, config: dict):
    """The final normed hidden rows [rows, hidden] of the rows ``ids`` at
    ``positions`` under the mask ``seen`` (float32 ``params``)."""
    x = params["embed"][ids]
    one = jax.checkpoint(lambda x, p: layer(x, p, positions, seen, config))
    # The period is one layer: leaves [layers, 1, ...] read as [layers,
    # ...] and scanned, so that the gradients are written into the stacked
    # leaves in place (a slice a layer inside the loop keeps every layer's
    # gradient as a temporary: 9.9 GiB for 5.7).
    x, _ = jax.lax.scan(
        lambda x, p: (one(x, p), None), x,
        jax.tree.map(lambda a: a[:, 0], params["period"]["0"]))
    return rmsnorm(x, params["ln_f"], config["rms_norm_eps"])


def token_nll(params, x, targets):
    """-log softmax(x W_head^T)[targets] of each row of x [rows, hidden]."""
    block = blocks_of(x.shape[0], LOGIT_BLOCK)

    @jax.checkpoint
    def some(args):
        x_rows, t_rows = args
        logp = jax.nn.log_softmax(x_rows @ params["head"].T, -1)
        return -jnp.take_along_axis(logp, t_rows[:, None], -1)[:, 0]

    return jax.lax.map(some, (x.reshape(-1, block, x.shape[1]),
                              targets.reshape(-1, block))).reshape(-1)


def noisy_nll(params, ids, masked, config: dict):
    """One sequence ``ids`` [L] with ``masked`` [L]: the L noisy rows'
    -log p(x_0,i) from one pass over [x_t ; x_0] (float32 ``params``)."""
    length = ids.shape[0]
    x_t = jnp.where(masked, config["vocab"] - 1, ids)
    positions = jnp.tile(jnp.arange(length), 2)
    x = hidden(params, jnp.concatenate([x_t, ids]), positions,
               visible(length, config["block_length"]), config)
    return token_nll(params, x[:length], ids)


def loss(params, tokens, t, masked, *, config: dict):
    """The block-diffusion loss of ``tokens`` [B, L] corrupted at the
    levels ``t`` [B, L / block] on the positions ``masked`` [B, L]."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)

        def sequence(args):
            ids, t_seq, masked_seq = args
            weight = masked_seq / jnp.repeat(t_seq, config["block_length"])
            return weight * noisy_nll(params, ids, masked_seq, config)

        return jax.lax.map(sequence, (tokens, t, masked)).mean()
