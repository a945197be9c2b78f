"""The dropless expert layer over held experts
(``parallel.moe.moe_held_experts``): against every expert applied to every
token and masked by the picks; the shares of a layer add up to the uncut
layer of ``benchmark/reference/laguna.py``; no pick is dropped under any
imbalance; what ``TransformerConfig.num_experts`` has always meant on one
device still holds; the two new gauges; the route saved across a layer's
recompute (PR 42)."""

import functools
import os
import re
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


# ---------------------------------------------------------------------------
# The route across a layer's recompute (PR 42): what a checkpoint that saves
# ``moe.ROUTE_SAVED`` keeps, and the cotangent from the picked scores alone.
# ---------------------------------------------------------------------------

ROUTES = [(score, normalize) for score in ("sigmoid", "softmax")
          for normalize in (True, False)]
KEEP_ROUTE = jax.checkpoint_policies.save_only_these_names(moe.ROUTE_SAVED)
WRAPS = {"none": lambda f: f, "bare": jax.checkpoint,
         "keep": functools.partial(jax.checkpoint, policy=KEEP_ROUTE)}
# Sizes that tell the router's product [TS, ES] from its cotangents
# [TS, DS] and [DS, ES].
LS, TS, DS, ES = 3, 64, 24, 16


def _stack(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    held = ES // 2
    return dict(
        x=jax.random.normal(ks[0], (TS, DS)),
        w_router=jax.random.normal(ks[1], (LS, DS, ES)),
        w_up=jax.random.normal(ks[2], (LS, held, DS, F)) * 0.3,
        w_gate=jax.random.normal(ks[3], (LS, held, DS, F)) * 0.3,
        w_down=jax.random.normal(ks[4], (LS, held, F, DS)) * 0.3)


def _scanned(w, wrap, **route):
    """Three sparse layers with residuals under ``lax.scan``, each under
    ``wrap``, holding experts 4 .. 11 of 16."""
    def layer(p, h):
        y, _ = moe.moe_held_experts(
            h, p["w_router"], p["w_up"], p["w_down"], p["w_gate"],
            experts_first=4, **route)
        return h + y

    body = wrap(layer)
    layers = {k: v for k, v in w.items() if k != "x"}
    out, _ = jax.lax.scan(lambda h, p: (body(p, h), None), w["x"], layers)
    return jnp.sum(out ** 2)


def _route_of(score, normalize):
    return dict(top_k=3, score=score, normalize=normalize, scale=2.5)


def _plain_route(x, w_router, *, top_k, score, normalize, scale,
                 select_bias=None, normalize_eps=0.0):
    """The route as autodiff alone differentiates it (no selection bias,
    no epsilon: tests/test_moe_select_bias.py has those)."""
    assert select_bias is None and not normalize_eps
    z = jnp.dot(x, w_router, precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(z) if score == "sigmoid" else \
        jax.nn.softmax(z, -1)
    picked, experts = jax.lax.top_k(scores, top_k)
    if normalize:
        picked = picked / picked.sum(-1, keepdims=True)
    return scores, experts, picked * scale


@pytest.mark.parametrize("score, normalize", ROUTES)
@pytest.mark.parametrize("wrap", ["bare", "keep"])
def test_the_checkpointed_layers_gradients_are_the_plain_layers(
        score, normalize, wrap):
    """Saving the route changes what the backward reads, not what it
    computes: every leaf bit-equal in float32 to the layers without a
    checkpoint (and a bare checkpoint's are too)."""
    w = _stack()
    route = _route_of(score, normalize)
    want = jax.jit(jax.grad(functools.partial(
        _scanned, wrap=WRAPS["none"], **route)))(w)
    got = jax.jit(jax.grad(functools.partial(
        _scanned, wrap=WRAPS[wrap], **route)))(w)
    for name in want:
        assert float(jnp.abs(want[name]).max()) > 0, name
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


@pytest.mark.parametrize("score, normalize", ROUTES)
def test_the_routes_cotangent_from_the_picked_scores_is_autodiffs(
        score, normalize, monkeypatch):
    """The rule that reads a token's k picked scores against the one that
    reads the whole row: the router's gradient and the tokens' to 1e-6 of
    the leaf's largest entry through the route alone; to 1e-5 through
    three layers with residuals, which carry a last-bit difference in a
    weight on through two more layers' products (the plain route stands in
    for ``moe.moe_route`` there)."""
    route = _route_of(score, normalize)
    w = _stack(1)
    c = jax.random.normal(jax.random.PRNGKey(7), (TS, 3))

    def through(fn):
        return jax.jit(jax.grad(lambda x, wr: jnp.sum(
            fn(x, wr, **route)[2] * c), argnums=(0, 1)))(
                w["x"], w["w_router"][0])

    for got, want in zip(through(moe.moe_route), through(_plain_route)):
        np.testing.assert_allclose(
            got, want, rtol=0, atol=1e-6 * float(jnp.abs(want).max()))
    got = jax.jit(jax.grad(functools.partial(
        _scanned, wrap=WRAPS["keep"], **route)))(w)
    monkeypatch.setattr(moe, "moe_route", _plain_route)
    want = jax.jit(jax.grad(functools.partial(
        _scanned, wrap=WRAPS["none"], **route)))(w)
    for name in want:
        np.testing.assert_allclose(
            got[name], want[name], rtol=0, err_msg=name,
            atol=1e-5 * float(jnp.abs(want[name]).max()))


@pytest.mark.parametrize("score, normalize", ROUTES)
def test_which_routes_take_the_rule_follows_their_own_arguments(
        score, normalize):
    """softmax scores that are not normalised over the picks need the
    row's other scores: autodiff's path.  Every other route takes the
    rule."""
    w = _stack()
    jaxpr = str(jax.make_jaxpr(lambda x, wr: moe.moe_route(
        x, wr, **_route_of(score, normalize)))(w["x"], w["w_router"][0]))
    general = score == "softmax" and not normalize
    assert ("custom_vjp_call" in jaxpr) == (not general)
    assert jaxpr.count(f"name={moe.ROUTE_SAVED}") == 2


def _route_instructions(hlo_text):
    """(op_name, the instruction's text, whether the benchmark's counter
    ``moe_route_sorts`` counts it) of the compiled program's instructions
    under ``hvdt.moe.route``."""
    from benchmark.layer_metrics import moe_route_sorts

    sorting = set(moe_route_sorts.sorts(hlo_text))
    found = []
    for line in hlo_text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if m and "hvdt.moe.route" in m.group(1):
            name = line.split(" = ")[0].split("%")[-1]
            found.append((m.group(1), line.strip(), name in sorting))
    return found


@pytest.mark.parametrize("score, normalize", ROUTES)
def test_the_backward_body_holds_no_sort_no_top_k_and_no_router_product(
        score, normalize):
    """The compiled gradient of three checkpointed layers.  Under a bare
    checkpoint the backward body runs the route again (two sorts, a top-k
    and the router's product more); under one that keeps the route's name
    the only sorts, the only top-k and the only product of the router's
    shape [T, E] are the forward's, and what the backward holds under
    ``hvdt.moe.route`` is the cotangent's two products (and, where the
    score's rule reads the whole row, the product that remakes it)."""
    w = _stack()
    route = _route_of(score, normalize)
    general = score == "softmax" and not normalize

    def compiled(wrap):
        return jax.jit(jax.grad(functools.partial(
            _scanned, wrap=WRAPS[wrap], **route))).lower(w).compile(
                ).as_text()

    def counts(text):
        route_ops = _route_instructions(text)
        backward = [op for op in route_ops if "transpose(" in op[0]]
        product = f"f32[{TS},{ES}]"
        return (sum(sorts for _, _, sorts in route_ops),
                sum(sorts for _, _, sorts in backward),
                sum(" dot(" in t and t.split(" = ")[1].startswith(product)
                    for _, t, _ in route_ops),
                sum(" dot(" in t for _, t, _ in backward))

    assert counts(compiled("bare")) == (6, 3, 2, 3)
    # forward: a top-k, two sorts, the product; backward: dx and dw
    assert counts(compiled("keep")) == (3, 0, 2 if general else 1,
                                        3 if general else 2)


def test_the_routes_cotangent_rule_stays_under_the_routes_scope():
    """``moe_dispatch_ms`` and ``moe_route_ms`` read ``hvdt.moe.route``:
    the rule's own instructions (the compare-and-sum that spreads the
    picks' cotangent over the experts, the two products) carry it in the
    backward as the forward's do."""
    w = _stack()
    text = jax.jit(jax.grad(functools.partial(
        _scanned, wrap=WRAPS["keep"], **_route_of("sigmoid", True)))
        ).lower(w).compile().as_text()
    backward = [(n, t) for n, t, _ in _route_instructions(text)
                if "transpose(" in n]
    assert sum(" dot(" in t for _, t in backward) == 2
    assert any("reduce" in t for _, t in backward)
    assert all(re.search(r"(?:^|[/(])hvdt\.moe\.route(?:$|[/)])", n)
               for n, _ in backward)
    # what the recompute still holds of the route is index arithmetic
    assert not any(sorts or " dot(" in t
                   for n, t, sorts in _route_instructions(text)
                   if "rematted_computation" in n)
