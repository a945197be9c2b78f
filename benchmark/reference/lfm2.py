"""Plain reference for the ``lfm2`` family: a decoder LM whose layers mix
the sequence by a double-gated short convolution, with a softmax-attention
layer among every few (LFM2: ``layer_types`` says which), the first
``num_dense_layers`` layers with a dense SwiGLU feed-forward and the rest
with a sparse one whose router picks by score plus a selection bias and
weighs by the score alone, a tied head, written from the layer equations in
straightforward ``jax.numpy``: float32 throughout,
``jax.default_matmul_precision("highest")``, the convolution as an explicit
sum over its shifted copies, attention's scores materialised under a dense
mask, every held expert applied to every row and masked by the picks, dense
logits, no kernels.  It imports nothing from ``horovod_tpu``.  It reads the
configuration file's own keys (the source's ``config.json`` names) and the
parameter pytree the system trains, so gradients compare leaf by leaf:

    params["lead"][i]     layer ``layers_first + i``, one each
    params["period"][r]   run r of the period (equal neighbours), leaves
                          stacked [periods, layers of the run, ...]
    params["embed"]       [vocab rows held, hidden], also the head
    params["ln_f"]

x [L, hidden]; N(x; w) = x / sqrt(mean(x^2) + norm_eps) * w; no bias
anywhere.  Every layer:

    h = x + Mixer(N(x; ln1));  x <- h + F(N(h; ln2))

``conv`` mixer (``layer_types[l] == "conv"``), h = N(x), d = hidden:

    [B | C | X] = h W_in            three blocks of d columns, in that order
    u = B * X
    v_t = sum_{i=0..taps-1} conv[i] * u[t - (taps - 1) + i]
                                    zeros left of the sequence; depthwise,
                                    one filter of ``conv_L_cache`` taps a
                                    channel; NO activation
    Mixer = (C * v) W_out

``full_attention`` mixer, 32 query / 8 key-value heads of 64:

    q = RoPE(N_head(h Wq; q_norm)), k = RoPE(N_head(h Wk; k_norm)), v = h Wv
    o_n = softmax(q_n k_m^T / 8 + causal mask) v_m,  m = n // 4
    Mixer = concat(o) Wo

N_head norms a head's 64 dimensions (eps ``norm_eps``) BEFORE the rotation;
RoPE rotates all 64 (rotate-half, pairs (i, i + 32)) by position *
theta^(-2i/64), theta ``rope_parameters.rope_theta``.

Dense feed-forward (layers below ``num_dense_layers``): F(h) = (silu(h
W_gate) * (h W_up)) W_down.  Sparse (the others):

    s = sigmoid(h W_r) in R^64
    I = the ``num_experts_per_tok`` largest of s + router_bias
    w_e = s_e / (sum_{e' in I} s_e' + 1e-6) * routed_scaling_factor
                                    the scores WITHOUT the bias
    F(h) = sum_{e in I, e held here} w_e (silu(h W_gate,e) * h W_up,e) W_down,e

    loss = mean_t -log softmax(N(x_L; ln_f) E^T)[tokens_t+1]

Departures from the published description, each an entry of the
configuration file's ``assumed``:

* the column order of ``W_in`` ([B | C | X]) and the tap order (the last
  tap weighs the token itself), as transformers' ``Lfm2ShortConv`` has
  them;
* the tied head (``tie_word_embeddings`` is not in the catalog row);
* ``router_bias`` is a constant of the loss: the balancing rule that moves
  it between steps is not published, and no gradient reaches it (it enters
  the picks alone, and a pick has no derivative);
* initial values are the system's own: this reference reads them and
  draws nothing;
* THE SHARE: the layers are ``layers_first .. layers_first + layers - 1``,
  only experts ``experts_first .. experts_first + experts - 1`` are held,
  so a sparse layer adds their part of the routed sum alone (what the
  absent experts would add is left out, here as in the system), and the
  vocabulary is its first ``vocab`` rows.

Memory is rescheduled and no operation or its order is changed: each layer
is under ``jax.checkpoint``, and inside it so are the scores, in blocks of
``QUERY_BLOCK`` query rows, the experts in steps of ``EXPERT_GROUP`` and
the logits in blocks of ``LOGIT_BLOCK`` rows.  The layers of a run are
scanned over their stacked leaves, which is the loop over them.
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


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def blocks_of(length: int, most: int) -> int:
    """The largest divisor of ``length`` that is at most ``most``."""
    return next(b for b in range(min(most, length), 0, -1)
                if length % b == 0)


def short_conv(h, p, config: dict):
    """The double-gated short convolution: h [L, hidden] -> [L, hidden]."""
    length = h.shape[0]
    b, c, x = jnp.split(h @ p["w_in"], 3, -1)
    u = b * x
    taps = p["conv"].shape[0]
    v = jnp.zeros_like(u)
    for i in range(taps):
        back = taps - 1 - i             # tap i reads the token ``back`` ago
        shifted = jnp.concatenate(
            [jnp.zeros((back, u.shape[1]), u.dtype), u[:length - back]])
        v = v + p["conv"][i] * shifted
    if "conv_bias" in p:
        v = v + p["conv_bias"]
    return (c * v) @ p["w_out"]


def rotate(x, theta: float):
    """x [L, H, D] at positions 0 .. L - 1: every dimension of a head
    rotates by position * theta^(-2i / D), pairs (i, i + D / 2)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(h, p, config: dict):
    """h [L, hidden] -> concat(o) Wo [L, hidden]."""
    length = h.shape[0]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    dh, eps = config["hidden_size"] // heads, config["norm_eps"]
    theta = config["rope_parameters"]["rope_theta"]
    group = heads // kv                 # query head n reads kv head n // group
    q = rotate(norm((h @ p["wq"]).reshape(length, heads, dh), p["q_norm"],
                    eps), theta).reshape(length, kv, group, dh)
    k = rotate(norm((h @ p["wk"]).reshape(length, kv, dh), p["k_norm"],
                    eps), theta)
    v = (h @ p["wv"]).reshape(length, kv, dh)
    block = blocks_of(length, QUERY_BLOCK)
    positions = jnp.arange(length)

    @jax.checkpoint
    def rows(args):
        q_rows, i = args                # [block, kv, group, D], [block]
        s = jnp.einsum("qngd,knd->ngqk", q_rows, k) / math.sqrt(dh)
        s = jnp.where(positions[None, :] <= i[:, None], s, -jnp.inf)
        return jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(rows, (q.reshape(-1, block, kv, group, dh),
                           positions.reshape(-1, block)))
    return o.reshape(length, heads * dh) @ p["wo"]


def dense(h, p):
    return (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


def sparse(h, p, config: dict):
    """The held experts' part of the routed sum (no shared expert)."""
    scores = jax.nn.sigmoid(h @ p["w_router"])                  # [L, E]
    chosen_by = scores + p["router_bias"] if config["use_expert_bias"] \
        else scores
    _, experts = jax.lax.top_k(jax.lax.stop_gradient(chosen_by),
                               config["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, experts, -1)          # WITHOUT bias
    if config["norm_topk_prob"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
    weights = weights * config["routed_scaling_factor"]
    first = config["experts_first"]

    @jax.checkpoint
    def some(acc, group):
        e, w_gate, w_up, w_down = group             # EXPERT_GROUP experts
        w = jnp.where(experts[None] == first + e[:, None, None], weights,
                      0.0).sum(-1)                  # [experts, L]
        mid = (jax.nn.silu(jnp.einsum("ld,edf->elf", h, w_gate))
               * jnp.einsum("ld,edf->elf", h, w_up))
        return acc + jnp.einsum("elf,efd,el->ld", mid, w_down, w), None

    held = p["w_up"].shape[0]
    size = math.gcd(held, EXPERT_GROUP)
    routed, _ = jax.lax.scan(some, jnp.zeros_like(h), jax.tree.map(
        lambda a: a.reshape((held // size, size) + a.shape[1:]),
        (jnp.arange(held), p["w_gate"], p["w_up"], p["w_down"])))
    return routed


def layer(x, p, index: int, config: dict):
    """Layer ``index`` of the published stack on x [L, hidden]."""
    eps = config["norm_eps"]
    mixer = {"conv": short_conv, "full_attention": attention}[
        config["layer_types"][index]]
    x = x + mixer(norm(x, p["ln1"], eps), p, config)
    h = norm(x, p["ln2"], eps)
    if index < config["num_dense_layers"]:
        return x + dense(h, p)
    return x + sparse(h, p, config)


def runs_in_order(params, first: int):
    """(index of the run's first layer, the run's parameters stacked
    [layers of the run, ...]), first run to last, period by period, from
    layer ``first`` of the published stack.  The layers of a run are of one
    kind (that is what makes them a run)."""
    index = first
    runs = [params["period"][r] for r in sorted(params["period"], key=int)]
    for period in range(jax.tree.leaves(runs[0])[0].shape[0]):
        for run in runs:
            yield index, jax.tree.map(lambda a: a[period], run)
            index += jax.tree.leaves(run)[0].shape[1]


def loss(params, tokens, *, config: dict):
    """Next-token cross entropy of ``tokens`` [B, L] under ``params``."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        first = config.get("layers_first", 0)
        leading = [params["lead"][i] for i in sorted(params["lead"], key=int)]

        def sequence(ids):
            x = params["embed"][ids]
            for i, p in enumerate(leading):
                x = jax.checkpoint(lambda x, p, index=first + i: layer(
                    x, p, index, config))(x, p)
            for index, run in runs_in_order(params, first + len(leading)):
                # A scan over the run's stacked layers, so that their
                # gradients are written into the stacked leaves in place.
                one = jax.checkpoint(
                    lambda x, p, index=index: layer(x, p, index, config))
                x, _ = jax.lax.scan(lambda x, p: (one(x, p), None), x, run)
            x = norm(x, params["ln_f"], config["norm_eps"])
            block = blocks_of(x.shape[0], LOGIT_BLOCK)

            @jax.checkpoint
            def rows(args):
                x_rows, targets = args
                logp = jax.nn.log_softmax(x_rows @ params["embed"].T, -1)
                return jnp.take_along_axis(logp, targets[:, None], -1)[:, 0]

            # Every row against the token after it; the last row has none
            # (it is scored against a stand-in and dropped).
            ll = jax.lax.map(rows, (x.reshape(-1, block, x.shape[1]),
                                    jnp.roll(ids, -1).reshape(-1, block)))
            return ll.reshape(-1)[:-1]

        return -jax.lax.map(sequence, tokens).mean()
