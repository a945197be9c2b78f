"""The Gated DeltaNet mixer's parts (``ops/gated_delta.py``): the chunked
scan against the delta rule run token by token (the recurrence of
``benchmark/reference/qwen3_next.py``: another derivation), values and the
gradients of q, k, v, g and beta, at lengths that are and are not whole
chunks (a ragged tail is padded, not refused); the convolution against
XLA's own; the scan under a ``shard_map`` and at another chunk length, on
both schedules of the inverse of I + A (``tests/test_gated_delta_inverse.py``
has the inverse itself and the ``schedule`` fixture); the operation count
against hand-worked numbers.  CPU only."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.reference import qwen3_next as reference  # noqa: E402
from horovod_tpu.ops import gated_delta as gd  # noqa: E402
from test_gated_delta_inverse import choose, jit, schedule  # noqa: E402,F401

B, HK, HV, DK, DV = 2, 2, 4, 16, 8


def operands(length, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (B, length, HK, DK), dtype),
            jax.random.normal(ks[1], (B, length, HK, DK), dtype),
            jax.random.normal(ks[2], (B, length, HV, DV), dtype),
            -jnp.exp(jax.random.normal(ks[3], (B, length, HV))),
            jax.nn.sigmoid(jax.random.normal(ks[4], (B, length, HV))))


def token_by_token(q, k, v, g, beta):
    """The reference's recurrence on the scan's operands: q and k
    normalised as the mixer normalises them, value heads grouped by the
    key head they read, one sequence at a time."""
    hk, r = q.shape[2], v.shape[2] // q.shape[2]
    grouped = lambda x: x.reshape(x.shape[:2] + (hk, r) + x.shape[3:])  # noqa: E731
    q = reference.l2norm(q) * q.shape[-1] ** -0.5
    with jax.default_matmul_precision("highest"):
        o = jax.vmap(reference.delta_rule)(
            q, reference.l2norm(k), grouped(v), grouped(g), grouped(beta))
    return o.reshape(v.shape)


@pytest.mark.parametrize("length", [64, 192, 100, 7],
                         ids=["one_chunk", "three_chunks", "ragged_tail",
                              "under_a_chunk"])
def test_the_chunked_scan_is_the_recurrence(length):
    args = operands(length)
    got = jax.jit(gd.gated_delta_rule)(*args)
    want = token_by_token(*args)
    assert got.shape == (B, length, HV, DV) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)

    def grads(fn):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(fn(*a) * jnp.cos(jnp.arange(DV))),
            argnums=(0, 1, 2, 3, 4)))(*args)

    for name, a, b in zip("q k v g beta".split(),
                          grads(gd.gated_delta_rule), grads(token_by_token)):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5 * scale,
                                   err_msg=name)


def test_a_chunk_size_is_only_a_schedule(schedule):
    """Chunks of 16 against 64, each on either schedule of the inverse: the
    whole rule through the kernel."""
    args = operands(96, seed=3)
    np.testing.assert_allclose(
        jit(functools.partial(gd.gated_delta_rule, chunk=16))(*args),
        jit(gd.gated_delta_rule)(*args), rtol=2e-5, atol=2e-6)


def test_strong_decays_underflow_to_zero_and_nothing_overflows():
    """g of -30 a token: gamma underflows inside the chunk; ratios are
    exps of differences, so nothing is inf or nan, forward or backward."""
    q, k, v, g, beta = operands(128, seed=5)
    g = g * 30.0
    (_, out), grads = jax.jit(jax.value_and_grad(
        lambda *a: (lambda o: (jnp.sum(o ** 2), o))(gd.gated_delta_rule(*a)),
        argnums=(0, 1, 2, 3, 4), has_aux=True))(q, k, v, g, beta)
    assert all(bool(jnp.isfinite(x).all()) for x in grads)
    np.testing.assert_allclose(out, token_by_token(q, k, v, g, beta),
                               rtol=2e-5, atol=2e-6)


def test_bfloat16_operands_keep_a_float32_state():
    q, k, v, g, beta = operands(256, seed=7)
    bf = lambda x: x.astype(jnp.bfloat16)  # noqa: E731
    want = token_by_token(q, k, v, g, beta)
    got = jax.jit(gd.gated_delta_rule)(bf(q), bf(k), bf(v), g, beta)
    rounded = jax.jit(functools.partial(
        gd.gated_delta_rule, carry_dtype=jnp.bfloat16))(
            bf(q), bf(k), bf(v), g, beta)
    err = lambda x: float(jnp.abs(x - want).max())  # noqa: E731
    assert got.dtype == jnp.float32
    assert err(got) < 0.05 * float(jnp.abs(want).max())
    assert err(rounded) >= err(got)


def test_the_convolution_is_xlas_depthwise_one():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 37, 12))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 12))
    want = lax.conv_general_dilated(
        x, w[:, None, :], window_strides=(1,), padding=[(3, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=12,
        precision=lax.Precision.HIGHEST)
    np.testing.assert_allclose(gd.causal_conv(x, w), want, rtol=1e-5,
                               atol=1e-5)
    # causal: output t reads inputs t-3 .. t, and the newest tap is the last
    y = gd.causal_conv(x.at[:, 20:].set(0.0), w)
    np.testing.assert_array_equal(y[:, :20], gd.causal_conv(x, w)[:, :20])
    np.testing.assert_allclose(gd.causal_conv(x, w)[:, 0], x[:, 0] * w[3],
                               rtol=1e-6)
    np.testing.assert_allclose(reference.causal_conv(x[0], w),
                               gd.causal_conv(x, w)[0], rtol=1e-5, atol=1e-5)


def test_the_gated_norm():
    o = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 8))
    z = jax.random.normal(jax.random.PRNGKey(1), (3, 5, 8))
    w = jnp.linspace(0.5, 1.5, 8)
    want = (o / np.sqrt(np.mean(np.square(o), -1, keepdims=True) + 1e-6)
            * w * (z / (1 + np.exp(-z))))
    np.testing.assert_allclose(gd.gated_rmsnorm(o, z, w), want, rtol=1e-5)


def test_the_scan_runs_inside_a_shard_map(devices, schedule):
    """The state's initial value takes the operands' varying axes (the
    benchmark's step is a shard_map over dp with every axis manual), and
    so does what the inverse's kernel returns."""
    mesh = Mesh(np.asarray(devices[:2]), ("dp",))
    args = operands(64)
    got = jax.jit(jax.shard_map(
        gd.gated_delta_rule, mesh=mesh, in_specs=(P("dp"),) * 5,
        out_specs=P("dp")))(*args)
    np.testing.assert_allclose(got, jax.jit(gd.gated_delta_rule)(*args),
                               rtol=1e-5, atol=1e-6)
    # and the gradient, through the inverse's own cotangent rule
    grad = lambda f: jax.jit(jax.grad(  # noqa: E731
        lambda *a: jnp.sum(f(*a) ** 2), argnums=(1, 4)))(*args)
    for a, b in zip(grad(jax.shard_map(
            gd.gated_delta_rule, mesh=mesh, in_specs=(P("dp"),) * 5,
            out_specs=P("dp"))), grad(gd.gated_delta_rule)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_the_scans_operations_by_hand():
    """C 64, dk = dv 128: a key head's two pair products 2 x 64^2 x 128, a
    value head's C^2 (dk + 2 dv) + 3 C dk dv + C^3 / 3; 16 key and 32
    value heads: 2.67M multiply-adds a token (2.93M were the pair products
    counted a value head)."""
    macs = gd.scan_macs_per_token(key_heads=16, value_heads=32, key_dim=128,
                                  value_dim=128)
    a_key_head = 2 * 64 * 64 * 128
    a_value_head = 64 * 64 * 384 + 3 * 64 * 128 * 128 + 64 ** 3 / 3
    assert macs == (16 * a_key_head + 32 * a_value_head) / 64
    assert macs / 1e6 == pytest.approx(2.665, abs=0.005)
    a_head = 64 * 64 * 640 + 3 * 64 * 128 * 128 + 64 ** 3 / 3
    assert 32 * a_head / 64 / 1e6 == pytest.approx(2.93, abs=0.01)
