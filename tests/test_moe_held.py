"""The dropless expert layer over held experts
(``parallel.moe.moe_held_experts``): against every expert applied to every
token and masked by the picks; the shares of a layer add up to the uncut
layer of ``benchmark/reference/laguna.py``; no pick is dropped under any
imbalance; what ``TransformerConfig.num_experts`` has always meant on one
device still holds; the two new gauges."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

T, D, F, E, K = 96, 16, 8, 16, 2


def _weights(seed=0, gated=True):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    return dict(
        x=jax.random.normal(ks[0], (T, D)),
        w_router=jax.random.normal(ks[1], (D, E)),
        w_up=jax.random.normal(ks[2], (E, D, F)) * 0.3,
        w_gate=jax.random.normal(ks[3], (E, D, F)) * 0.3 if gated else None,
        w_down=jax.random.normal(ks[4], (E, F, D)) * 0.3,
        ws_up=jax.random.normal(ks[5], (D, F)) * 0.3,
        ws_gate=jax.random.normal(ks[6], (D, F)) * 0.3,
        ws_down=jax.random.normal(ks[7], (F, D)) * 0.3)


def _expert(x, w, e):
    up = x @ w["w_up"][e]
    mid = jax.nn.silu(up) if w["w_gate"] is None else \
        jax.nn.silu(x @ w["w_gate"][e]) * up
    return mid @ w["w_down"][e]


def _every_expert_on_every_token(w, first, held, **route):
    """sum over held picks of weight * expert(x), the plain way."""
    _, experts, weights = moe.moe_route(w["x"], w["w_router"], **route)
    out = jnp.zeros_like(w["x"])
    for e in range(first, first + held):
        mine = jnp.where(experts == e, weights, 0.0).sum(-1)
        out = out + mine[:, None] * _expert(w["x"], w, e)
    return out


def _layer(w, first, held, shared_fn=None, **route):
    rows = slice(first, first + held)
    return moe.moe_held_experts(
        w["x"], w["w_router"], w["w_up"][rows], w["w_down"][rows],
        None if w["w_gate"] is None else w["w_gate"][rows],
        experts_first=first, shared_fn=shared_fn, **route)


@pytest.mark.parametrize("first, held", [(0, 4), (4, 4), (12, 4), (0, 16)])
@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_the_held_part_of_the_routed_sum_and_its_gradients(first, held,
                                                          score):
    w = _weights()
    route = dict(top_k=K, score=score, scale=2.5)
    got, aux = jax.jit(lambda w: _layer(w, first, held, **route))(w)
    want = _every_expert_on_every_token(w, first, held, **route)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert float(aux.dropped_fraction) == 0.0
    _, experts, _ = moe.moe_route(w["x"], w["w_router"], **route)
    landed = np.asarray((experts >= first) & (experts < first + held))
    assert float(aux.held_rows) == landed.sum()
    assert float(aux.max_expert_rows) == max(
        int((np.asarray(experts) == e).sum())
        for e in range(first, first + held))

    def grads(fn):
        return jax.grad(lambda w: jnp.sum(fn(w) ** 2))

    g_got = jax.jit(grads(lambda w: _layer(w, first, held, **route)[0]))(w)
    g_want = grads(lambda w: _every_expert_on_every_token(
        w, first, held, **route))(w)
    for name in ("x", "w_router", "w_up", "w_gate", "w_down"):
        np.testing.assert_allclose(g_got[name], g_want[name], rtol=2e-4,
                                   atol=2e-4, err_msg=name)


@pytest.mark.parametrize("model, shares, route", [
    ("laguna", 4, dict(top_k=K, score="sigmoid", scale=2.5)),
    ("qwen3_next", 16, dict(top_k=3, score="softmax")),
    ("sdar", 8, dict(top_k=4, score="softmax"))])
def test_the_shares_add_up_to_the_uncut_reference_layer(model, shares, route):
    """The shares of a layer (four of four experts each as Laguna's eight
    chips would hold them; sixteen of one each as Qwen3-Next's sixteen;
    eight of two each as SDAR's eight), the shared expert counted once
    (Qwen3-Next's times its sigmoid gate; SDAR has none to count): their
    parts sum to what the model's plain reference under benchmark/reference
    gives for the whole layer (all 16 experts held)."""
    import importlib

    reference = importlib.import_module(f"benchmark.reference.{model}")
    w = _weights(3)
    held = E // shares
    p = {k: w[k] for k in ("w_router", "w_up", "w_gate", "w_down", "ws_up",
                           "ws_gate", "ws_down")}
    if model == "qwen3_next":
        p["ws_sg"] = jax.random.normal(jax.random.PRNGKey(9), (D,))

    def shared(h):
        y = (jax.nn.silu(h @ w["ws_gate"]) * (h @ w["ws_up"])) @ w["ws_down"]
        if "ws_sg" in p:
            y = y * jax.nn.sigmoid(h @ p["ws_sg"])[:, None]
        return y

    parts = [jax.jit(lambda w, first=first: _layer(
        w, first, held,
        shared_fn=shared if first == 0 and model != "sdar" else None,
        **route)[0])(w) for first in range(0, E, held)]
    with jax.default_matmul_precision("highest"):
        if model == "laguna":
            whole = reference.sparse(w["x"], p, per_token=K, scaling=2.5,
                                     first=0)
        else:
            whole = reference.sparse(w["x"], p, per_token=route["top_k"],
                                     first=0, normalise=True)
    np.testing.assert_allclose(sum(parts), whole, rtol=2e-5, atol=2e-5)
    # and one share alone is not the layer
    assert float(jnp.abs(parts[0] - whole).max()) > 1e-2


@pytest.fixture()
def telemetry(monkeypatch):
    from horovod_tpu.telemetry import instrument as ti
    from horovod_tpu.telemetry import metrics as tm

    monkeypatch.setenv("HVDT_TELEMETRY", "1")
    ti.reset()
    tm.reset_default_registry()
    yield ti.get_recorder()
    ti.reset()
    tm.reset_default_registry()


def test_no_pick_is_dropped_when_every_token_picks_the_same_expert(
        telemetry):
    """The worst imbalance: every token's first pick is expert 5 (a held
    one).  A capacity dispatcher at any factor under E would drop most of
    them; here all T rows land on it and the result is still exact."""
    w = _weights(5)
    # A router that scores expert 5 highest for every token.
    w["w_router"] = jnp.zeros((D, E)).at[:, 5].set(jnp.sign(w["x"]).mean(0))
    w["x"] = jnp.abs(w["x"]) * jnp.sign(w["w_router"][:, 5])[None]
    route = dict(top_k=1, score="softmax", normalize=False)
    _, experts, _ = moe.moe_route(w["x"], w["w_router"], **route)
    assert (np.asarray(experts) == 5).all()
    got, aux = jax.jit(lambda w: _layer(w, 4, 4, **route))(w)
    want = _every_expert_on_every_token(w, 4, 4, **route)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert float(aux.held_rows) == T and float(aux.max_expert_rows) == T
    moe.report_moe_aux(aux)
    gauges = telemetry.registry
    assert gauges.get("hvdt_moe_dropped_fraction").value() == 0.0
    assert gauges.get("hvdt_moe_held_rows").value() == T
    assert gauges.get("hvdt_moe_max_expert_rows").value() == T


def test_every_pick_on_held_experts_fills_the_row_buffer_to_its_bound():
    """T x k rows is the buffer's static bound and it is reached when all
    experts are held: still no drop."""
    w = _weights(6)
    route = dict(top_k=4, score="sigmoid", scale=1.0)
    got, aux = jax.jit(lambda w: _layer(w, 0, E, **route))(w)
    assert float(aux.held_rows) == T * 4
    np.testing.assert_allclose(
        got, _every_expert_on_every_token(w, 0, E, **route), rtol=1e-5,
        atol=1e-5)


def test_the_uniform_configurations_expert_layer_keeps_its_numbers():
    """``num_experts`` on one device was: softmax, top-1 by argmax, the
    pick weighted by its probability, silu(x W_up) W_down, every expert run
    on every token.  The model now runs the dropless layer at one pick over
    all experts held; the numbers are the old ones."""
    from horovod_tpu.models.transformer import (TransformerConfig, _moe_mlp,
                                                transformer_init)

    cfg = TransformerConfig(vocab=64, layers=1, d_model=D, heads=2,
                            kv_heads=2, d_ff=F, dtype=jnp.float32,
                            num_experts=4)
    p = jax.tree.map(lambda a: a[0], transformer_init(
        jax.random.PRNGKey(0), cfg)["block"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, D))

    def old(p, x):
        tokens = x.reshape(-1, D)
        probs = jax.nn.softmax(tokens @ p["w_router"], -1)
        top = jnp.argmax(probs, -1)
        gate = jnp.take_along_axis(probs, top[:, None], 1)[:, 0]
        hmid = jax.nn.silu(jnp.einsum("nd,edf->enf", tokens, p["w_up"]))
        all_out = jnp.einsum("enf,efd->end", hmid, p["w_down"])
        sel = jnp.take_along_axis(all_out, top[None, :, None], 0)[0]
        return (sel * gate[:, None]).reshape(x.shape)

    got, aux = jax.jit(lambda p, x: _moe_mlp(p, x, cfg))(p, x)
    np.testing.assert_allclose(got, jax.jit(old)(p, x), rtol=1e-5,
                               atol=1e-5)
    assert float(aux.held_rows) == 48
    g_got = jax.jit(jax.grad(
        lambda p: jnp.sum(_moe_mlp(p, x, cfg)[0] ** 2)))(p)
    g_old = jax.jit(jax.grad(lambda p: jnp.sum(old(p, x) ** 2)))(p)
    for name in ("w_router", "w_up", "w_down"):
        np.testing.assert_allclose(g_got[name], g_old[name], rtol=2e-4,
                                   atol=2e-4, err_msg=name)


def test_an_unknown_score_function_is_refused():
    w = _weights()
    with pytest.raises(ValueError, match="sigmoid"):
        moe.moe_route(w["x"], w["w_router"], top_k=2, score="tanh")
