"""The double-gated short convolution (``ops.short_conv.gated_short_conv``,
LFM2's ``conv`` mixer): against a loop a token that carries the last two
``u = B * X``; causality; the two gates inside ``causal_conv`` against the
products written out; what ``causal_conv`` computed before it took gates,
bit for bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.gated_delta import causal_conv
from horovod_tpu.ops.short_conv import gated_short_conv

B, L, D = 2, 24, 16


def _leaves(seed=0, taps=3, bias=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    p = {"w_in": jax.random.normal(ks[0], (D, 3 * D)) * D ** -0.5,
         "conv": jax.random.uniform(ks[1], (taps, D), minval=-0.5,
                                    maxval=0.5),
         "w_out": jax.random.normal(ks[2], (D, D)) * D ** -0.5}
    if bias:
        p["conv_bias"] = jax.random.uniform(ks[3], (D,), minval=-0.5,
                                            maxval=0.5)
    return p, jax.random.normal(ks[4], (B, L, D))


def _proj(x, w):
    return x @ w.astype(x.dtype)


def _a_token_at_a_time(x, p):
    """The mixer as a decoder would run it: the state is the last ``taps -
    1`` tokens of ``u``, zeros before the sequence."""
    taps = p["conv"].shape[0]
    bias = p.get("conv_bias", 0.0)
    out = np.zeros(x.shape, np.float64)
    w_in, conv, w_out = (np.asarray(p[n], np.float64)
                         for n in ("w_in", "conv", "w_out"))
    for n in range(x.shape[0]):
        state = np.zeros((taps - 1, D))
        for t in range(x.shape[1]):
            b, c, xs = np.split(np.asarray(x[n, t], np.float64) @ w_in, 3)
            window = np.concatenate([state, (b * xs)[None]])
            v = (conv * window).sum(0) + bias
            out[n, t] = (c * v) @ w_out
            state = window[1:]
    return out


@pytest.mark.parametrize("taps,bias", [(3, False), (3, True), (4, False),
                                       (1, False)])
def test_the_mixer_against_a_loop_that_carries_the_last_tokens_of_u(taps,
                                                                    bias):
    p, x = _leaves(taps=taps, bias=bias)
    got = jax.jit(lambda x, p: gated_short_conv(x, p, proj=_proj))(x, p)
    np.testing.assert_allclose(got, _a_token_at_a_time(x, p), rtol=2e-5,
                               atol=2e-6)


@pytest.mark.parametrize("t", [0, 7, L - 1])
def test_a_change_at_token_t_moves_no_output_before_t(t):
    p, x = _leaves(seed=1)
    f = jax.jit(lambda x: gated_short_conv(x, p, proj=_proj))
    moved = f(x.at[:, t].add(1.0)) - f(x)
    assert float(jnp.abs(moved[:, :t]).max(initial=0.0)) == 0.0
    # and it reaches t, t + 1 and t + 2 (three taps) and no further
    reached = jnp.abs(moved).max((0, 2)) > 0
    assert list(np.flatnonzero(reached)) == list(range(t, min(t + 3, L)))


def test_the_gradients_against_the_products_written_out():
    p, x = _leaves(seed=2, bias=True)

    def plain(x, p):
        b, c, xs = jnp.split(x @ p["w_in"], 3, -1)
        u = jnp.pad(b * xs, ((0, 0), (2, 0), (0, 0)))
        v = sum(p["conv"][i] * u[:, i:i + L] for i in range(3)) \
            + p["conv_bias"]
        return (((c * v) @ p["w_out"]) ** 2).sum()

    def ours(x, p):
        return (gated_short_conv(x, p, proj=_proj) ** 2).sum()

    got = jax.jit(jax.grad(ours, (0, 1)))(x, p)
    want = jax.jit(jax.grad(plain, (0, 1)))(x, p)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)


def test_the_result_is_in_the_compute_dtype_and_the_gates_in_float32():
    p, x = _leaves(seed=3)
    x16 = x.astype(jnp.bfloat16)
    got = gated_short_conv(x16, p, proj=_proj)
    assert got.dtype == jnp.bfloat16
    # float32 inside: the gated convolution of bf16 B, C, X rounds once
    b, c, xs = (_proj(x16, p["w_in"][:, i * D:(i + 1) * D])
                for i in range(3))
    one = causal_conv(xs, p["conv"], times=b, gate=c)
    exact = causal_conv(xs.astype(jnp.float32), p["conv"],
                        times=b.astype(jnp.float32),
                        gate=c.astype(jnp.float32))
    assert one.dtype == jnp.bfloat16
    np.testing.assert_array_equal(one, exact.astype(jnp.bfloat16))


@pytest.mark.parametrize("bias", [False, True])
def test_causal_conv_without_gates_is_what_it_was_bit_for_bit(bias):
    """The form the Gated DeltaNet and the Mamba-2 mixers call."""
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(ks[0], (B, L, D)).astype(jnp.bfloat16)
    w = jax.random.normal(ks[1], (4, D))
    b = jax.random.normal(ks[2], (D,)) if bias else None

    def as_it_was(x, w, bias):
        taps, l = w.shape[0], x.shape[1]
        padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
        w = w.astype(jnp.float32)
        y = sum(padded[:, i:i + l].astype(jnp.float32) * w[i]
                for i in range(taps))
        if bias is not None:
            y = y + bias.astype(jnp.float32)
        return y.astype(x.dtype)

    np.testing.assert_array_equal(causal_conv(x, w, b), as_it_was(x, w, b))
    text = [jax.jit(f).lower(x, w, b).as_text()
            for f in (causal_conv, as_it_was)]
    strip = [t.replace("as_it_was", "causal_conv") for t in text]
    assert strip[0] == strip[1]
