"""Plain reference for the ``qwen3_next`` family: a decoder LM whose layers
alternate three Gated DeltaNet (linear-attention) layers with one gated
full-attention layer, every layer with a sparse feed-forward (softmax
router, a gated shared expert), zero-centred RMSNorms and an untied head,
written from the layer equations in straightforward ``jax.numpy``: float32
throughout, ``jax.default_matmul_precision("highest")``, the delta rule as
the recurrence token by token, scores materialised, every held expert
applied to every token, dense logits, no kernels.  It imports nothing from
``horovod_tpu``.  It reads the configuration file's own keys (the source's
``config.json`` names) and the parameter pytree the system trains, so
gradients compare leaf by leaf:

    params["period"][r]        run r of the period (run 0 the three linear
                               layers, run 1 the full one), leaves stacked
                               [periods, layers of the run, ...]
    params["embed"], ["head"]  [vocab rows held, hidden]; params["ln_f"]

x [L, 2048]; RMS(x) = x / sqrt(mean(x^2) + rms_norm_eps); N(x; w) =
RMS(x) (1 + w); no biases.  Every layer: x <- x + Mixer(N(x; ln1)), then
x <- x + MoE(N(x; ln2)).

Full layer (layer l with (l + 1) % full_attention_interval == 0), h = N(x):

    [q | gate] = h Wq          per head 256 query then 256 gate columns
    k = h Wk, v = h Wv         [L, 2, 256]
    q_n = RoPE(N(q_n; q_norm)), k_m = RoPE(N(k_m; k_norm))   the norm over a
        head's 256, then its first 64 dimensions rotate (rotate-half, pairs
        (i, i + 32), theta 1e7)
    s_ij = q_i . k_j / sqrt(256), j <= i; query head n reads kv head n // 8
    o_n = sigmoid(gate_n) * (softmax(s) v)_n;  Mixer = concat(o) Wo

Linear layer (Gated DeltaNet), h = N(x):

    [q | k | v | z] = h Wqkvz;  [b | a] = h Wba
    [q | k | v] <- silu(causal depthwise conv, 4 taps, left pad 3, no bias)
    16 key heads of 128, 32 value heads of 128; value head n, key head n // 2
    q_n <- q_n / |q_n| / sqrt(128),  k_n <- k_n / |k_n|     (eps 1e-6)
    beta_t = sigmoid(b_t);  g_t = -exp(A_log) softplus(a_t + dt_bias)
    a value head, S in R^{128 x 128}, S_0 = 0:
        S' = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S'^T k_t)
        S_t = S' + k_t u_t^T;   o_t = S_t^T q_t
    Mixer = concat_n(RMS(o_n) * gdn_norm * silu(z_n)) Wout

MoE, h = N(x): p = softmax(h Wr) over all num_experts; I = top
num_experts_per_tok of p; w_e = p_e / sum_{e' in I} p_e';
    MoE = sum_{e in I, e held here} w_e E_e(h) + sigmoid(h . ws_sg) E_shared(h)
    E(h) = (silu(h W_gate) * (h W_up)) W_down
loss = mean_t -log softmax(N(x_L; ln_f) W_head^T)[tokens_t+1]

Departures from the published description, each an entry of the
configuration file's ``assumed``:

* the column order inside ``Wqkvz`` and ``Wba`` ([q | k | v | z] and
  [b | a] as blocks; the source interleaves them by key head: with seeded
  random weights any fixed order is the same model);
* initial values: ``A_log`` = log(u) with u uniform in [1e-3, 16),
  ``dt_bias`` 1, the gains of the zero-centred norms 0, ``gdn_norm`` 1;
* no multi-token-prediction module;
* THE SHARE: only experts ``experts_first .. experts_first + experts - 1``
  are held, so a layer adds their part of the routed sum alone (what the
  absent experts would add is left out, here as in the system), and the
  vocabulary is its first ``vocab`` rows.

Memory is rescheduled and no operation or its order is changed: each layer
is under ``jax.checkpoint``, and inside a linear layer so is what turns h
into the rule's operands (the projections, the convolution, the norms: 2.7
GB of float32 at 16,384 tokens, recomputed in the backward); value heads
read their key head by broadcasting, not from a repeated copy; the token
scan is nested (an outer
checkpointed scan over blocks of ``TOKEN_BLOCK`` tokens, an inner one over
tokens: 16,384 saved states of 32 x 128 x 128 floats would be 34 GB); the
scores are computed in blocks of ``QUERY_BLOCK`` query rows, the experts in
steps of ``EXPERT_GROUP``, the logits in blocks of ``LOGIT_BLOCK`` rows,
each block recomputed in the backward.  The layers of a run are scanned
over their stacked leaves, which is the loop over them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# Memory only (the check runs beside the weights and two gradient trees in
# 16 GB).
TOKEN_BLOCK = 128
QUERY_BLOCK = 128
EXPERT_GROUP = 8
LOGIT_BLOCK = 2048


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def blocks_of(length: int, most: int) -> int:
    """The largest divisor of ``length`` that is at most ``most``."""
    return next(b for b in range(min(most, length), 0, -1)
                if length % b == 0)


def rotate(x, theta: float, rotated: int):
    """x [L, H, D]: the first ``rotated`` dimensions of a head rotate by
    t * theta^(-2i / rotated), pairs (i, i + rotated / 2)."""
    half = rotated // 2
    freq = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / rotated)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotated], x[..., rotated:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest],
                           -1)


def full_attention(h, p, config: dict):
    """h [L, hidden] -> concat(o) Wo [L, hidden]."""
    length = h.shape[0]
    dh, kv = config["head_dim"], config["num_key_value_heads"]
    heads = config["num_attention_heads"]
    eps = config["rms_norm_eps"]
    rotated = int(dh * config["partial_rotary_factor"])
    q, gate = jnp.split((h @ p["wq"]).reshape(length, heads, 2 * dh), 2, -1)
    q = rotate(norm(q, p["q_norm"], eps), config["rope_theta"], rotated)
    k = rotate(norm((h @ p["wk"]).reshape(length, kv, dh), p["k_norm"], eps),
               config["rope_theta"], rotated)
    v = (h @ p["wv"]).reshape(length, kv, dh)
    group = heads // kv                 # query head n reads kv head n // group
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
    o = o.reshape(length, heads, dh) * jax.nn.sigmoid(gate)
    return o.reshape(length, heads * dh) @ p["wo"]


def delta_rule(q, k, v, g, beta):
    """The gated delta rule as the recurrence, token by token.  q, k [L,
    Hk, dk] (normalised already), v [L, Hk, R, dv], g, beta [L, Hk, R]:
    value head (n, r) reads key head n -> o [L, Hk, R, dv]; the state S
    [Hk, R, dk, dv] starts at 0."""
    length = q.shape[0]
    block = blocks_of(length, TOKEN_BLOCK)

    def token(s, x):
        q_t, k_t, v_t, g_t, beta_t = x
        s = jnp.exp(g_t)[:, :, None, None] * s
        u = beta_t[:, :, None] * (v_t - jnp.einsum("nrde,nd->nre", s, k_t))
        s = s + k_t[:, None, :, None] * u[:, :, None, :]
        return s, jnp.einsum("nrde,nd->nre", s, q_t)

    @jax.checkpoint
    def some(s, xs):
        return jax.lax.scan(token, s, xs)

    s0 = jnp.zeros(v.shape[1:3] + (q.shape[2], v.shape[3]), q.dtype)
    _, o = jax.lax.scan(some, s0, jax.tree.map(
        lambda a: a.reshape((length // block, block) + a.shape[1:]),
        (q, k, v, g, beta)))
    return o.reshape((length,) + o.shape[2:])


def causal_conv(x, w):
    """x [L, C], w [taps, C]: y_t = sum_i w[i] x[t - (taps - 1) + i], zeros
    left of the sequence."""
    taps, length = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return sum(padded[i:i + length] * w[i] for i in range(taps))


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def linear_attention(h, p, config: dict):
    """The Gated DeltaNet mixer: h [L, hidden] -> [L, hidden]."""
    length = h.shape[0]
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    kw, vw, r = hk * dk, hv * dv, hv // hk

    @jax.checkpoint                     # memory only: see the docstring
    def operands(h, p):
        qkvz = h @ p["w_qkvz"]
        ba = h @ p["w_ba"]
        qkv = jax.nn.silu(causal_conv(qkvz[:, :2 * kw + vw], p["conv"]))
        q = l2norm(qkv[:, :kw].reshape(length, hk, dk)) / math.sqrt(dk)
        k = l2norm(qkv[:, kw:2 * kw].reshape(length, hk, dk))
        # value head n reads key head n // r: value heads as [Hk, R]
        v = qkv[:, 2 * kw:].reshape(length, hk, r, dv)
        z = qkvz[:, 2 * kw + vw:].reshape(length, hk, r, dv)
        beta = jax.nn.sigmoid(ba[:, :hv]).reshape(length, hk, r)
        g = (-jnp.exp(p["a_log"]) * jax.nn.softplus(ba[:, hv:] + p["dt_bias"])
             ).reshape(length, hk, r)
        return q, k, v, z, g, beta

    q, k, v, z, g, beta = operands(h, p)
    o = delta_rule(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-6)
    o = o * p["gdn_norm"] * jax.nn.silu(z)
    return o.reshape(length, vw) @ p["w_out"]


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def sparse(h, p, *, per_token: int, first: int, normalise: bool):
    """The held experts' part of the routed sum, plus the gated shared
    expert."""
    scores = jax.nn.softmax(h @ p["w_router"], -1)              # [L, E]
    weights, experts = jax.lax.top_k(scores, per_token)
    if normalise:
        weights = weights / weights.sum(-1, keepdims=True)      # [L, k]

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
    return routed + jax.nn.sigmoid(h @ p["ws_sg"])[:, None] * swiglu(
        h, p["ws_gate"], p["ws_up"], p["ws_down"])


def layer(x, p, index: int, config: dict):
    """Layer ``index`` of the published stack on x [L, hidden]."""
    eps = config["rms_norm_eps"]
    full = (index + 1) % config["full_attention_interval"] == 0
    mixer = full_attention if full else linear_attention
    x = x + mixer(norm(x, p["ln1"], eps), p, config)
    return x + sparse(norm(x, p["ln2"], eps), p,
                      per_token=config["num_experts_per_tok"],
                      first=config["experts_first"],
                      normalise=config["norm_topk_prob"])


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
            x = params["embed"][ids]
            for index, run in runs_in_order(params):
                # A scan over the run's stacked layers, so that their
                # gradients are written into the stacked leaves in place.
                one = jax.checkpoint(
                    lambda x, p, index=index: layer(x, p, index, config))
                x, _ = jax.lax.scan(lambda x, p: (one(x, p), None), x, run)
            x = norm(x, params["ln_f"], config["rms_norm_eps"])[:-1]
            block = blocks_of(x.shape[0], LOGIT_BLOCK)

            @jax.checkpoint
            def rows(args):
                x_rows, targets = args
                logp = jax.nn.log_softmax(x_rows @ params["head"].T, -1)
                return jnp.take_along_axis(logp, targets[:, None], -1)[:, 0]

            return jax.lax.map(rows, (x.reshape(-1, block, x.shape[1]),
                                      ids[1:].reshape(-1, block)))

        return -jax.lax.map(sequence, tokens).mean()
