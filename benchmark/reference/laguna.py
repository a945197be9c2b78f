"""Plain reference for the ``laguna`` family: a decoder LM whose layers
follow a published pattern (full and sliding-window attention with their
own head counts and rotary settings, a per-head output gate, one leading
dense feed-forward and sparse ones with a shared expert after it, an
untied head), written from the layer equations in straightforward
``jax.numpy``: float32 throughout, ``jax.default_matmul_precision
("highest")``, scores materialised, every held expert applied to every
token, dense logits, no kernels.  It imports nothing from ``horovod_tpu``.
It reads the configuration file's own keys (the source's ``config.json``
names) and the parameter pytree the system trains, so gradients compare
leaf by leaf:

    params["lead"][i]          layer i, i < number of leading layers
    params["period"][r]        run r of the period, leaves stacked
                               [periods, layers of the run, ...]
    params["embed"], ["head"]  [vocab rows held, hidden]; params["ln_f"]

Layer l of kind kappa = layer_types[l], H = num_attention_heads_per_layer[l]
(x [L, 2048]; every RMSNorm has eps rms_norm_eps and a gain; no biases):

    h   = RMSNorm(x)
    q   = RoPE_kappa(h Wq) [L, H, 128];  k = RoPE_kappa(h Wk), v = h Wv [L, 8, 128]
    s_ij = q_i . k_j / sqrt(128)  for j <= i, and for sliding layers also
           i - j < sliding_window;  query head n reads kv head n // (H / 8)
    o_n = sigmoid(h Wg)_n * (softmax(s) v)_n ;  x <- x + concat(o) Wo
    h   = RMSNorm(x)
    dense:  x <- x + (silu(h W_gate) * (h W_up)) W_down
    sparse: s = sigmoid(h Wr) in R^num_experts;  I = top num_experts_per_tok
            of s;  w_e = moe_routed_scaling_factor * s_e / sum_{e' in I} s_e'
            x <- x + sum_{e in I, e held here} w_e E_e(h) + E_shared(h)
    loss = mean_t -log softmax(RMSNorm(x_L) W_head^T)[tokens_t+1]

RoPE: sliding layers rotate all 128 dimensions of a head (rotate-half,
pairs (i, i + 64)) by t * theta^(-2i/128), theta 10,000.  Full layers rotate
the first 64 (partial_rotary_factor 0.5; pairs (i, i + 32)), theta 500,000,
with YaRN's frequencies as ``transformers`` computes them (static in the
length) and cos, sin times attention_factor.

Departures from the published description, each an entry of the
configuration file's ``assumed``:

* the router's score function (sigmoid, normalised over the picks, times
  the scaling factor) and the absence of a selection bias: ``config.json``
  names neither;
* ``gating`` read as a per-head sigmoid gate from the layer's normed input;
* no q/k norm; ``sliding_window`` counts the query's own position;
* THE SHARE: only experts ``experts_first .. experts_first + experts - 1``
  are held, so a sparse layer adds their part of the sum alone (what the
  absent experts would add is left out, here as in the system), and the
  vocabulary is its first ``vocab`` rows.

Two departures from "no remat" reschedule memory and leave every operation
and its order alone: each layer is under ``jax.checkpoint``, and inside it
so are the query blocks of the scores (48 heads x 8192^2 x 4 B is 12.9 GB
whole) and the steps of the experts' loop (eight experts each).  The
layers of a run are scanned over their stacked leaves, which is the loop
over them and no other mathematics.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# Memory only (the check runs beside the weights and two gradient trees in
# 16 GB): query rows a block of scores, experts a step of their loop.
QUERY_BLOCK = 128
EXPERT_GROUP = 8


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope_tables(rope: dict, head_dim: int, length: int):
    """(cos, sin) [length, rotated / 2] of one layer type's
    ``rope_parameters`` entry, the attention factor folded in."""
    dim = int(head_dim * rope.get("partial_rotary_factor", 1))
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    freq = rope["rope_theta"] ** (-2.0 * i / dim)
    factor = 1.0
    if rope.get("rope_type", "default") == "yarn":
        theta, original = rope["rope_theta"], \
            rope["original_max_position_embeddings"]

        def correction(rotations):
            return (dim * math.log(original / (rotations * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(correction(rope["beta_fast"])), 0)
        high = min(math.ceil(correction(rope["beta_slow"])), dim - 1)
        ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
        freq = freq / rope["factor"] * ramp + freq * (1.0 - ramp)
        factor = rope["attention_factor"]
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * freq
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor


def rotate(x, cos, sin):
    """x [L, H, D]: the first 2 * cos.shape[-1] dimensions rotate, pair
    (i, i + half); the rest pass through."""
    half = cos.shape[-1]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest],
                           -1)


def attention(h, p, *, head_dim, kv_heads, window, rope, gated):
    """h [L, hidden] -> concat(o) Wo [L, hidden]."""
    length = h.shape[0]
    heads = p["wq"].shape[-1] // head_dim
    cos, sin = rope_tables(rope, head_dim, length)
    q = rotate((h @ p["wq"]).reshape(length, heads, head_dim), cos, sin)
    k = rotate((h @ p["wk"]).reshape(length, kv_heads, head_dim), cos, sin)
    v = (h @ p["wv"]).reshape(length, kv_heads, head_dim)
    group = heads // kv_heads           # query head n reads kv head n // group
    block = min(QUERY_BLOCK, length)
    positions = jnp.arange(length)

    @jax.checkpoint
    def rows(args):
        q_rows, i = args                # [block, kv, group, D], [block]
        s = jnp.einsum("qngd,knd->ngqk", q_rows, k) / math.sqrt(head_dim)
        seen = positions[None, :] <= i[:, None]
        if window is not None:
            seen = seen & (i[:, None] - positions[None, :] < window)
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(rows, (q.reshape(-1, block, kv_heads, group, head_dim),
                           positions.reshape(-1, block)))
    o = o.reshape(length, heads, head_dim)
    if gated:
        o = o * jax.nn.sigmoid(h @ p["wg"])[:, :, None]
    return o.reshape(length, heads * head_dim) @ p["wo"]


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def sparse(h, p, *, per_token, scaling, first):
    """The held experts' part of the routed sum, plus the shared expert."""
    scores = jax.nn.sigmoid(h @ p["w_router"])                  # [L, E]
    picked, experts = jax.lax.top_k(scores, per_token)
    weights = scaling * picked / picked.sum(-1, keepdims=True)  # [L, k]

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
    return routed + swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"])


def layer(x, p, index: int, config: dict):
    """Layer ``index`` of the published stack on x [L, hidden]."""
    kind = config["layer_types"][index]
    eps = config["rms_norm_eps"]
    x = x + attention(
        rmsnorm(x, p["ln1"], eps), p, head_dim=config["head_dim"],
        kv_heads=config["num_key_value_heads"],
        window=config["sliding_window"]
        if kind == "sliding_attention" else None,
        rope=config["rope_parameters"][kind], gated=config["gating"])
    h = rmsnorm(x, p["ln2"], eps)
    if config["mlp_layer_types"][index] == "dense":
        return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
    return x + sparse(h, p, per_token=config["num_experts_per_tok"],
                      scaling=config["moe_routed_scaling_factor"],
                      first=config["experts_first"])


def runs_in_order(params):
    """(index of the run's first layer, the run's parameters stacked
    [layers of the run, ...]), first run to last: each leading layer by
    itself, then period by period the period's runs.  The layers of a run
    are of one kind (that is what makes them a run)."""
    index = 0
    for i in sorted(params["lead"], key=int):
        yield index, jax.tree.map(lambda a: a[None], params["lead"][i])
        index += 1
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
            logits = rmsnorm(x, params["ln_f"],
                             config["rms_norm_eps"]) @ params["head"].T
            logp = jax.nn.log_softmax(logits[:-1], -1)
            return jnp.take_along_axis(logp, ids[1:, None], -1)

        return -jax.lax.map(sequence, tokens).mean()
