"""Plain reference for the ``granite_hybrid`` family: a decoder LM whose
layers are Mamba-2 state-space mixers with a softmax-attention layer among
every few (Granite 4.0-H: ``layer_types`` says which), every layer with a
dense SwiGLU feed-forward, four constant multipliers and a tied head,
written from the layer equations in straightforward ``jax.numpy``: float32
throughout, ``jax.default_matmul_precision("highest")``, the state-space
layer from its definition as the masked sum over earlier tokens (no chunk
states, no recurrence carried in blocks), attention's scores materialised
under a dense mask, dense logits, no kernels.  It imports nothing from
``horovod_tpu``.  It reads the configuration file's own keys (the source's
``config.json`` names) and the parameter pytree the system trains, so
gradients compare leaf by leaf:

    params["period"][r]   run r of the period (equal neighbours of
                          ``layer_types``), leaves stacked [periods,
                          layers of the run, ...]
    params["embed"]       [vocab rows held, hidden], also the head
    params["ln_f"]

x [L, hidden]; N(x; w) = x / sqrt(mean(x^2) + rms_norm_eps) * w; no biases
but the convolution's.  With e, r, s, t = ``embedding_multiplier``,
``residual_multiplier``, ``attention_multiplier``, ``logits_scaling``:

    x_0 = e E[tokens]
    every layer: h = x + r Mixer(N(x; ln1));  x <- h + r W_down(silu(h'
        W_gate) * (h' W_up)), h' = N(h; ln2)
    loss = mean_t -log softmax(N(x_L; ln_f) E^T / t)[tokens_t+1]

Attention layer (``layer_types[l] == "attention"``), h = N(x):

    q = h Wq [L, 32, 64];  k = h Wk, v = h Wv [L, 8, 64];  NO rotary and no
    other position term (``position_embedding_type`` "nope")
    o_n = softmax(s q_n k_m^T + causal mask) v_m,  m = n // 4;  Mixer =
    concat(o) Wo

Mamba-2 layer (``"mamba"``), h = N(x); H heads of P over a state of N, G
groups (here 64, 64, 128, 1), I = H P:

    [z | xBC | dt] = h W_in            widths I, I + 2 G N, H
    xBC <- silu(conv(xBC) + conv_bias) causal, depthwise, 4 taps, left pad 3
    [x | B | C] = xBC                  x [L, H, P]; B, C [L, G, N], shared
                                       by the H / G heads of a group
    delta_t,h = softplus(dt_t,h + dt_bias_h)      (no clamp: the published
                                       time-step limits are (0, inf))
    log a_t,h = -exp(A_log_h) delta_t,h
    y_i,h = sum_{j<=i} (C_i . B_j) exp(sum_{j<k<=i} log a_k,h) delta_j,h
            x_j,h  +  D_h x_i,h
    u = y * silu(z);  Mixer = (u / sqrt(mean_group(u^2) + rms_norm_eps) *
    ssd_norm) W_out                    the mean over a group's I / G
                                       channels: all 4096 at one group

which is the recurrence ``S_t = a_t S_{t-1} + delta_t x_t B_t^T``, ``y_t =
S_t C_t + D x_t`` with ``S_0 = 0``, summed out.

Departures from the published description, each an entry of the
configuration file's ``assumed``:

* the column order of ``W_in`` ([z | x | B | C | dt]) and of the
  convolution's channels ([x | B | C]), and the gate inside the norm
  (``N(y silu(z))``), as the ``transformers`` implementation of
  ``granitemoehybrid`` has them;
* initial values: ``A_log`` = log(1 .. H), ``D`` = 1, the convolution's
  taps and bias uniform in +-1/2, ``dt_bias`` the inverse softplus of a
  time step drawn log-uniform in [1e-3, 1e-1] (the Mamba-2 code's; the
  weights are the system's own, so this reference reads them and draws
  nothing);
* THE SHARE: the vocabulary is its first ``vocab`` rows, and the layers
  are the first ``layers``.

One thing is computed with care and is the same mathematics: the exponent
``sum_{j<k<=i} log a_k`` of a block of query rows is taken as a difference
of running sums that start at the block's first row (forward for the keys
from there on, backward for the keys before it), not of running sums from
the sequence's start: at 8,192 tokens those reach 1e5 and more, and a
float32 difference of two of them would lose the digits that a decay
between neighbours is made of.

Memory is rescheduled and no operation or its order is changed: each layer
is under ``jax.checkpoint``; the state-space sum and the attention scores
are computed in blocks of ``QUERY_BLOCK`` query rows, the logits in blocks
of ``LOGIT_BLOCK`` rows, each block recomputed in the backward.  The
layers of a run are scanned over their stacked leaves, which is the loop
over them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Memory only (the check runs beside the weights and two gradient trees in
# 16 GB).
QUERY_BLOCK = 128
LOGIT_BLOCK = 2048


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def blocks_of(length: int, most: int) -> int:
    """The largest divisor of ``length`` that is at most ``most``."""
    return next(b for b in range(min(most, length), 0, -1)
                if length % b == 0)


def attention(h, p, config: dict):
    """h [L, hidden] -> concat(o) Wo [L, hidden]; no position term."""
    length = h.shape[0]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    dh = config["hidden_size"] // heads
    group = heads // kv                 # query head n reads kv head n // group
    q = (h @ p["wq"]).reshape(length, kv, group, dh)
    k = (h @ p["wk"]).reshape(length, kv, dh)
    v = (h @ p["wv"]).reshape(length, kv, dh)
    block = blocks_of(length, QUERY_BLOCK)
    positions = jnp.arange(length)

    @jax.checkpoint
    def rows(args):
        q_rows, i = args                # [block, kv, group, D], [block]
        s = jnp.einsum("qngd,knd->ngqk", q_rows, k) \
            * config["attention_multiplier"]
        s = jnp.where(positions[None, :] <= i[:, None], s, -jnp.inf)
        return jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(rows, (q.reshape(-1, block, kv, group, dh),
                           positions.reshape(-1, block)))
    return o.reshape(length, heads * dh) @ p["wo"]


def state_space(x, delta, log_a, b, c):
    """y_i = sum_{j<=i} (C_i . B_j) exp(sum_{j<k<=i} log a_k) delta_j x_j.
    x [L, G, R, P] (head (g, r) reads group g), delta and log_a [L, G, R],
    b, c [L, G, N] -> y [L, G, R, P]."""
    length = x.shape[0]
    block = blocks_of(length, QUERY_BLOCK)
    positions = jnp.arange(length)

    @jax.checkpoint
    def rows(args):
        c_rows, i = args                # [block, G, N], [block]
        # since[j] = sum_{k<=j} log a_k - sum_{k<first} log a_k, each side of
        # the block's first row a running sum of its own (the docstring)
        before = (positions < i[0])[:, None, None]
        forward = jnp.cumsum(jnp.where(before, 0.0, log_a), 0)
        backward = jnp.cumsum(jnp.where(before, log_a, 0.0)[::-1], 0)[::-1]
        since = forward - jnp.where(before, backward - log_a, 0.0)
        exponent = (jax.lax.dynamic_slice_in_dim(since, i[0], block)[:, None]
                    - since[None])      # [block, L, G, R]
        seen = (positions[None, :] <= i[:, None])[:, :, None, None]
        weights = (jnp.einsum("qgn,jgn->qjg", c_rows, b)[..., None]
                   * jnp.exp(jnp.where(seen, exponent, -jnp.inf)) * delta)
        return jnp.einsum("qjgr,jgrp->qgrp", weights, x)

    y = jax.lax.map(rows, (c.reshape((-1, block) + c.shape[1:]),
                           positions.reshape(-1, block)))
    return y.reshape(x.shape)


def causal_conv(x, w, bias):
    """x [L, C], w [taps, C]: y_t = sum_i w[i] x[t - (taps - 1) + i] +
    bias, zeros left of the sequence."""
    taps, length = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return sum(padded[i:i + length] * w[i] for i in range(taps)) + bias


def mamba(h, p, config: dict):
    """The Mamba-2 mixer: h [L, hidden] -> [L, hidden]."""
    length = h.shape[0]
    heads, dh = config["mamba_n_heads"], config["mamba_d_head"]
    groups, state = config["mamba_n_groups"], config["mamba_d_state"]
    inner, bc, r = heads * dh, groups * state, heads // groups
    z, xbc, dt = jnp.split(h @ p["w_in"], [inner, 2 * inner + 2 * bc], -1)
    xbc = jax.nn.silu(causal_conv(xbc, p["conv"], p["conv_bias"]))
    x = xbc[:, :inner].reshape(length, groups, r, dh)
    b = xbc[:, inner:inner + bc].reshape(length, groups, state)
    c = xbc[:, inner + bc:].reshape(length, groups, state)
    delta = jax.nn.softplus(dt + p["dt_bias"]).reshape(length, groups, r)
    log_a = -jnp.exp(p["a_log"]).reshape(groups, r) * delta
    y = state_space(x, delta, log_a, b, c) \
        + p["d_skip"].reshape(groups, r, 1) * x
    u = y.reshape(length, groups, inner // groups) \
        * jax.nn.silu(z.reshape(length, groups, inner // groups))
    u = norm(u, p["ssd_norm"].reshape(groups, inner // groups),
             config["rms_norm_eps"])
    return u.reshape(length, inner) @ p["w_out"]


def layer(x, p, index: int, config: dict):
    """Layer ``index`` of the published stack on x [L, hidden]."""
    eps, r = config["rms_norm_eps"], config["residual_multiplier"]
    mixer = {"attention": attention, "mamba": mamba}[
        config["layer_types"][index]]
    x = x + r * mixer(norm(x, p["ln1"], eps), p, config)
    h = norm(x, p["ln2"], eps)
    return x + r * ((jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"]))
                    @ p["w_down"])


def runs_in_order(params):
    """(index of the run's first layer, the run's parameters stacked
    [layers of the run, ...]), first run to last, period by period.  The
    layers of a run are of one kind (that is what makes them a run)."""
    index = 0
    runs = [params["period"][r] for r in sorted(params["period"], key=int)]
    for period in range(jax.tree.leaves(runs[0])[0].shape[0]):
        for run in runs:
            yield index, jax.tree.map(lambda a: a[period], run)
            index += jax.tree.leaves(run)[0].shape[1]


def loss(params, tokens, *, config: dict):
    """Next-token cross entropy of ``tokens`` [B, L] under ``params``."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)

        def sequence(ids):
            x = config["embedding_multiplier"] * params["embed"][ids]
            for index, run in runs_in_order(params):
                # A scan over the run's stacked layers, so that their
                # gradients are written into the stacked leaves in place.
                one = jax.checkpoint(
                    lambda x, p, index=index: layer(x, p, index, config))
                x, _ = jax.lax.scan(lambda x, p: (one(x, p), None), x, run)
            x = norm(x, params["ln_f"], config["rms_norm_eps"])
            block = blocks_of(x.shape[0], LOGIT_BLOCK)

            @jax.checkpoint
            def rows(args):
                x_rows, targets = args
                logp = jax.nn.log_softmax(
                    x_rows @ params["embed"].T / config["logits_scaling"],
                    -1)
                return jnp.take_along_axis(logp, targets[:, None], -1)[:, 0]

            # Every row against the token after it; the last row has none
            # (it is scored against a stand-in and dropped).
            ll = jax.lax.map(rows, (x.reshape(-1, block, x.shape[1]),
                                    jnp.roll(ids, -1).reshape(-1, block)))
            return ll.reshape(-1)[:-1]

        return -jax.lax.map(sequence, tokens).mean()
