"""A router whose picks and weights come from different numbers
(``parallel.moe.moe_route`` with ``select_bias``, LFM2's expert layer): the
picks follow score + bias, the weights the scores alone; ``normalize_eps``
to float32's limit; no gradient reaches the bias; without a bias the
numbers and the lowered program are what they were."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel import moe

T, D, F, E, K = 64, 16, 8, 16, 4


def _inputs(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        x=jax.random.normal(ks[0], (T, D)),
        w_router=jax.random.normal(ks[1], (D, E)),
        bias=jax.random.normal(ks[2], (E,)) * 0.3,
        w_up=jax.random.normal(ks[3], (E, D, F)) * 0.3,
        w_gate=jax.random.normal(ks[4], (E, D, F)) * 0.3,
        w_down=jax.random.normal(ks[5], (E, F, D)) * 0.3)


def _plain_route(x, w_router, bias, score, eps):
    """numpy, float64: the published block's lines."""
    z = np.asarray(x, np.float64) @ np.asarray(w_router, np.float64)
    s = 1 / (1 + np.exp(-z)) if score == "sigmoid" else \
        np.exp(z - z.max(-1, keepdims=True)) / np.exp(
            z - z.max(-1, keepdims=True)).sum(-1, keepdims=True)
    chosen = np.argsort(-(s + np.asarray(bias, np.float64)), -1)[:, :K]
    picked = np.take_along_axis(s, chosen, -1)
    return chosen, picked / (picked.sum(-1, keepdims=True) + eps)


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_picks_follow_score_plus_bias_and_weights_follow_the_score(score):
    w = _inputs()
    scores, experts, weights = jax.jit(
        lambda x, r, b: moe.moe_route(x, r, top_k=K, score=score,
                                      select_bias=b, normalize_eps=1e-6)
    )(w["x"], w["w_router"], w["bias"])
    chosen, want = _plain_route(w["x"], w["w_router"], w["bias"], score, 1e-6)
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(chosen, -1))
    order = np.argsort(experts, -1), np.argsort(chosen, -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(weights), order[0], -1),
        np.take_along_axis(want, order[1], -1), rtol=2e-6)
    # the bias changed the picks of many rows, or the test shows nothing
    plain = jax.lax.top_k(scores, K)[1]
    moved = (np.sort(plain, -1) != np.sort(experts, -1)).any(-1).mean()
    assert moved > 0.2
    # and weighing by score + bias would be another number
    biased = np.take_along_axis(np.asarray(scores) + np.asarray(w["bias"]),
                                np.asarray(experts), -1)
    assert np.abs(biased / biased.sum(-1, keepdims=True)
                  - np.asarray(weights)).max() > 1e-2


def test_normalize_eps_is_added_to_the_sum_to_float32s_limit():
    """Small scores, so that 1e-6 beside their sum is far above float32's
    rounding: weights sum to S / (S + eps), not to 1."""
    w = _inputs(1)
    ones = jnp.ones((T, D))
    router = jnp.full((D, E), -12.0 / D)        # every score sigmoid(-12)
    _, _, weights = moe.moe_route(ones, router, top_k=K,
                                  select_bias=w["bias"], normalize_eps=1e-6)
    s = 1 / (1 + np.exp(12.0))
    want = (K * s) / (K * s + 1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), want, rtol=1e-5)
    assert abs(want - 1.0) > 0.03               # eps shows
    _, _, accepted = moe.moe_route(ones, router, top_k=K)
    np.testing.assert_allclose(np.asarray(accepted).sum(-1), 1.0, rtol=1e-6)


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_no_gradient_reaches_the_bias_and_the_routers_is_autodiffs(score):
    w = _inputs(2)
    held = slice(4, 12)

    def layer(w_router, bias, x):
        out, _ = moe.moe_held_experts(
            x, w_router, w["w_up"][held], w["w_down"][held],
            w["w_gate"][held], top_k=K, experts_first=4, score=score,
            select_bias=bias, normalize_eps=1e-6)
        return (out ** 2).sum()

    def plain(w_router, bias, x):
        z = x @ w_router
        s = jax.nn.sigmoid(z) if score == "sigmoid" else \
            jax.nn.softmax(z, -1)
        experts = jax.lax.top_k(jax.lax.stop_gradient(s) + bias, K)[1]
        picked = jnp.take_along_axis(s, experts, -1)
        weights = picked / (picked.sum(-1, keepdims=True) + 1e-6)
        out = jnp.zeros_like(x)
        for e in range(4, 12):
            mine = jnp.where(experts == e, weights, 0.0).sum(-1)
            mid = jax.nn.silu(x @ w["w_gate"][e]) * (x @ w["w_up"][e])
            out = out + mine[:, None] * (mid @ w["w_down"][e])
        return (out ** 2).sum()

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(layer, (0, 1, 2)))(
            w["w_router"], w["bias"], w["x"])
        want = jax.jit(jax.grad(plain, (0, 1, 2)))(
            w["w_router"], w["bias"], w["x"])
    assert float(jnp.abs(got[1]).max()) == 0.0 and got[1].shape == (E,)
    assert float(jnp.abs(want[1]).max()) == 0.0
    np.testing.assert_allclose(got[0], want[0], rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(got[2], want[2], rtol=2e-4, atol=1e-6)
    assert float(jnp.abs(got[0]).max()) > 1e-3


@pytest.mark.parametrize("score,normalize", [
    ("sigmoid", True), ("sigmoid", False), ("softmax", True),
    ("softmax", False)])
def test_without_a_bias_the_numbers_are_todays_bit_for_bit(score, normalize):
    """``select_bias=None``, ``normalize_eps=0``: the route as it stood
    (written out here), value and gradient, and a bias of zeros beside it
    picks the same experts."""
    w = _inputs(3)

    def as_it_was(x, w_router):
        z = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(z) if score == "sigmoid" else \
            jax.nn.softmax(z, -1)
        picked, experts = jax.lax.top_k(s, K)
        if normalize:
            picked = picked / jnp.maximum(picked.sum(-1, keepdims=True),
                                          1e-20)
        return experts, picked * 2.5

    def ours(x, w_router, **kw):
        return moe.moe_route(x, w_router, top_k=K, score=score,
                             normalize=normalize, scale=2.5, **kw)[1:]

    # op by op: the same operations in the same order give the same bits
    # (under jit XLA may fuse the two spellings differently by an ulp)
    got = ours(w["x"], w["w_router"])
    want = as_it_was(w["x"], w["w_router"])
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    mix = jax.random.normal(jax.random.PRNGKey(9), (T, K))
    g_got = jax.jit(jax.grad(lambda r: (ours(w["x"], r)[1] * mix).sum()))(
        w["w_router"])
    g_want = jax.jit(jax.grad(
        lambda r: (as_it_was(w["x"], r)[1] * mix).sum()))(w["w_router"])
    np.testing.assert_allclose(g_got, g_want, rtol=1e-4, atol=1e-6)
    zeros = ours(w["x"], w["w_router"], select_bias=jnp.zeros((E,)))
    np.testing.assert_array_equal(zeros[0], want[0])
    np.testing.assert_array_equal(zeros[1], want[1])


def test_the_traced_routes_are_counted_by_what_chose_the_picks(monkeypatch):
    from horovod_tpu.telemetry import instrument, metrics

    w = _inputs(4)
    registry = metrics.MetricsRegistry()
    monkeypatch.setattr(instrument, "get_recorder",
                        lambda: instrument.CollectiveRecorder(registry))
    jax.jit(lambda x, r: moe.moe_route(x, r, top_k=K)[2])(
        w["x"], w["w_router"])
    for _ in range(2):
        jax.jit(lambda x, r, b: moe.moe_route(
            x, r, top_k=K, select_bias=b)[2]).lower(
            w["x"], w["w_router"], w["bias"])
    count = registry.get("hvdt_moe_routes_total")
    assert count.value(select="score") == 1
    assert count.value(select="score_plus_bias") == 2
    assert metrics.CATALOG["hvdt_moe_routes_total"].labels == ("select",)
