"""The Mamba-2 mixer's scan (``horovod_tpu/ops/ssd.py``): the chunked form
against the recurrence a token, in float32, forward and gradients; the
convolution's bias and the gated norm with the gate inside."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from horovod_tpu.ops.gated_delta import causal_conv, gated_rmsnorm
from horovod_tpu.ops.ssd import mamba2_mixer, scan_macs_per_token, ssd_scan


def recurrence(x, delta, a, b, c):
    """S_t = exp(a delta_t) S_{t-1} + delta_t x_t B_t^T, y_t = S_t C_t, a
    token at a time: x [B, L, H, P], delta [B, L, H], a [H], b and c [B, L,
    G, N], head n on group n // (H / G)."""
    heads, groups = x.shape[2], b.shape[2]
    b, c = (jnp.repeat(t, heads // groups, axis=2) for t in (b, c))

    def token(s, at):
        x_t, d_t, b_t, c_t = at
        s = (jnp.exp(a * d_t)[..., None, None] * s
             + (d_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t)

    s0 = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], jnp.float32)
    _, y = lax.scan(token, s0, tuple(jnp.moveaxis(t, 1, 0)
                                     for t in (x, delta, b, c)))
    return jnp.moveaxis(y, 0, 1)


def operands(length, heads=4, groups=1, head_dim=8, state=16, batch=2,
             seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (batch, length, heads, head_dim)),
            jax.nn.softplus(jax.random.normal(ks[1], (batch, length, heads))),
            -jnp.exp(jax.random.normal(ks[2], (heads,)) - 2.0),
            jax.random.normal(ks[3], (batch, length, groups, state)),
            jax.random.normal(ks[4], (batch, length, groups, state)))


# chunk, length, groups: whole chunks (more than two, so that a state is
# carried through a chunk it neither enters nor leaves at), every head on
# one B and C or two groups of them, and a length that is no whole chunk.
CASES = [(64, 256, 1), (256, 768, 1), (64, 192, 2), (64, 200, 1),
         (256, 300, 2)]


@pytest.mark.parametrize("chunk,length,groups", CASES)
def test_the_chunked_scan_is_the_recurrence(chunk, length, groups):
    args = operands(length, groups=groups)
    got = jax.jit(lambda *a: ssd_scan(*a, chunk=chunk))(*args)
    want = jax.jit(recurrence)(*args)
    assert got.dtype == jnp.float32 and got.shape == args[0].shape
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("chunk,length,groups", CASES)
def test_the_chunked_scans_gradients_are_the_recurrences(chunk, length,
                                                         groups):
    args = operands(length, groups=groups, seed=1)
    weights = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def scalar(fn):
        return jax.jit(jax.grad(
            lambda *a: (fn(*a) * weights).sum(), argnums=(0, 1, 2, 3, 4)))

    got = scalar(lambda *a: ssd_scan(*a, chunk=chunk))(*args)
    want = scalar(recurrence)(*args)
    for name, g, w in zip(("x", "delta", "a", "b", "c"), got, want):
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-4 * scale,
                                   err_msg=name)


def test_heads_of_a_group_share_its_b_and_c():
    """Two groups: a change to group 1's B moves the heads of group 1 and
    no head of group 0."""
    x, delta, a, b, c = operands(128, heads=4, groups=2, seed=2)
    run = jax.jit(lambda b: ssd_scan(x, delta, a, b, c, chunk=64))
    moved = run(b.at[:, :, 1].add(1.0)) - run(b)
    assert float(jnp.abs(moved[:, :, :2]).max()) == 0.0
    assert float(jnp.abs(moved[:, :, 2:]).max()) > 0.1


def test_a_state_lives_across_chunks():
    """Slow decays (a time step of about 1e-2, as the model draws them):
    what the first chunk wrote is read in the fourth, at the recurrence's
    value."""
    x, delta, a, b, c = operands(256, seed=3)
    delta = 0.01 * delta
    more = x.at[:, :64].add(1.0)
    run = jax.jit(lambda x: ssd_scan(x, delta, a, b, c, chunk=64))
    moved = (run(more) - run(x))[:, 192:]
    want = (recurrence(more, delta, a, b, c)
            - recurrence(x, delta, a, b, c))[:, 192:]
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(moved, want, rtol=1e-3, atol=1e-4)


def test_the_convolution_takes_a_bias():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    bias = jax.random.normal(jax.random.PRNGKey(2), (6,))
    np.testing.assert_allclose(causal_conv(x, w, bias),
                               causal_conv(x, w) + bias, rtol=1e-6)
    # causal: the first output sees the first input alone, on the last tap
    np.testing.assert_allclose(causal_conv(x, w, bias)[:, 0],
                               x[:, 0] * w[3] + bias, rtol=1e-6)


def test_the_gated_norm_with_the_gate_inside_or_outside():
    o = jax.random.normal(jax.random.PRNGKey(0), (3, 2, 8))
    z = jax.random.normal(jax.random.PRNGKey(1), (3, 2, 8))
    w = jax.random.normal(jax.random.PRNGKey(2), (2, 8))

    def rms(t, eps):
        return t / jnp.sqrt(jnp.mean(t * t, -1, keepdims=True) + eps)

    np.testing.assert_allclose(
        gated_rmsnorm(o, z, w, eps=1e-5, gate_first=True),
        rms(o * jax.nn.silu(z), 1e-5) * w, rtol=1e-6)
    np.testing.assert_allclose(gated_rmsnorm(o, z, w[0]),
                               rms(o, 1e-6) * w[0] * jax.nn.silu(z),
                               rtol=1e-6)


def test_the_mixer_is_its_definition():
    """``mamba2_mixer`` against the equations op by op, the scan as the
    recurrence: the column blocks of ``w_in``, the convolution's bias, no
    clamp on delta, ``D x``, the norm over a group's channels with the gate
    inside."""
    d, heads, dh, state, groups, length = 24, 4, 8, 16, 2, 96
    inner, bc = heads * dh, groups * state
    ks = iter(jax.random.split(jax.random.PRNGKey(4), 12))
    p = {"w_in": jax.random.normal(next(ks), (d, 2 * inner + 2 * bc + heads))
         * d ** -0.5,
         "conv": jax.random.uniform(next(ks), (4, inner + 2 * bc),
                                    minval=-0.5, maxval=0.5),
         "conv_bias": jax.random.uniform(next(ks), (inner + 2 * bc,),
                                         minval=-0.5, maxval=0.5),
         "a_log": jnp.log(jnp.arange(1.0, heads + 1)),
         "d_skip": jax.random.normal(next(ks), (heads,)),
         "dt_bias": jax.random.normal(next(ks), (heads,)),
         "ssd_norm": jax.random.normal(next(ks), (inner,)),
         "w_out": jax.random.normal(next(ks), (inner, d)) * inner ** -0.5}
    x = jax.random.normal(next(ks), (2, length, d))
    got = jax.jit(lambda x, p: mamba2_mixer(
        x, p, heads=heads, head_dim=dh, state=state, groups=groups, chunk=32,
        eps=1e-5, proj=lambda a, w: a @ w))(x, p)

    z, xbc, dt = jnp.split(x @ p["w_in"], [inner, 2 * inner + 2 * bc], -1)
    xbc = jax.nn.silu(causal_conv(xbc, p["conv"]) + p["conv_bias"])
    xs = xbc[..., :inner].reshape(2, length, heads, dh)
    b = xbc[..., inner:inner + bc].reshape(2, length, groups, state)
    c = xbc[..., inner + bc:].reshape(2, length, groups, state)
    delta = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(xs, delta, -jnp.exp(p["a_log"]), b, c) \
        + p["d_skip"][:, None] * xs
    u = (y.reshape(2, length, inner) * jax.nn.silu(z)).reshape(
        2, length, groups, inner // groups)
    u = u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + 1e-5)
    want = (u.reshape(2, length, inner) * p["ssd_norm"]) @ p["w_out"]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_the_count_of_multiply_adds():
    # granite-4.0-h-micro's: 256 x 128 once, 64 heads x (256 x 64 + 2 x 64
    # x 128)
    assert scan_macs_per_token(heads=64, head_dim=64, state=128, groups=1,
                               chunk=256) == 2_129_920
