"""Plain reference for the ``transformer_lm`` family: a causal decoder LM
written from the layer equations in straightforward ``jax.numpy`` —
float32 throughout, ``jax.default_matmul_precision("highest")``, the full
score square, dense logits, no kernels and no chunked loss.  It imports
nothing from ``horovod_tpu``; it reads the same parameter pytree the
system trains (leaves stacked ``[layers, ...]``) so gradients compare leaf
by leaf.

    x_0   = E[tokens]
    a_l   = x_l + Attn(RMSNorm(x_l; g1_l))          pre-norm residual
    x_l+1 = a_l + W_down(SiLU(W_gate n) * W_up n),  n = RMSNorm(a_l; g2_l)
    Attn(n): q, k = RoPE(n Wq), RoPE(n Wk) per head (rotate-half pairing
             (i, i + d/2), theta 10000), v = n Wv,
             softmax(q k^T / sqrt(d_head) + causal mask) v, then Wo
    logits = RMSNorm(x_L; g_f) E^T                   tied output head
    loss   = mean_t -log softmax(logits_t)[tokens_t+1]   (last position dropped)

One departure from "no remat": ``checkpoint_layers=True`` wraps each layer
in ``jax.checkpoint``.  That reschedules memory and leaves every operation
and its order alone; the seq-4096 sample needs it (one layer's f32 score
square is 1 GiB per sequence, 24 of them with their softmax do not fit).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

RMS_EPS = 1e-6      # the system's constant (models/transformer._rmsnorm)


def rmsnorm(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + RMS_EPS) * g


def rope(x, theta):
    """x: [B, L, H, D]; position t rotates pair (i, i + D/2) by
    t * theta^(-2i/D)."""
    d2 = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(d2, dtype=jnp.float32) / d2)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(x, p, heads: int, theta: float):
    b, l, d = x.shape
    dh = d // heads
    n = rmsnorm(x, p["ln1"])
    q = rope((n @ p["wq"]).reshape(b, l, heads, dh), theta)
    k = rope((n @ p["wk"]).reshape(b, l, heads, dh), theta)
    v = (n @ p["wv"]).reshape(b, l, heads, dh)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(dh))
    s = jnp.where(jnp.tril(jnp.ones((l, l), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    x = x + o.reshape(b, l, d) @ p["wo"]
    n = rmsnorm(x, p["ln2"])
    return x + (jax.nn.silu(n @ p["w_gate"]) * (n @ p["w_up"])) @ p["w_down"]


def loss(params, tokens, *, heads: int, rope_theta: float = 10000.0,
         checkpoint_layers: bool = False):
    """Next-token cross entropy of ``tokens`` [B, L] under ``params``."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = params["embed"][tokens]
        body = lambda x, p: layer(x, p, heads, rope_theta)  # noqa: E731
        if checkpoint_layers:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(lambda x, p: (body(x, p), None), x,
                            params["block"])
        logits = rmsnorm(x, params["ln_f"]) @ params["embed"].T
        logp = jax.nn.log_softmax(logits[:, :-1], -1)
        picked = jnp.take_along_axis(logp, tokens[:, 1:, None], -1)
        return -picked.mean()
