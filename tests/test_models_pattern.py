"""A layer-pattern TransformerConfig (leading layers, a period of kinds
with their own heads, windows and rotary settings, an output gate, dense
and sparse feed-forwards, an untied head over held vocabulary rows)
against the plain reference ``benchmark/reference/laguna.py`` in float32:
the same model to rounding, on the XLA path and on the kernels'."""

import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.layer_metrics import moe_route_sorts  # noqa: E402
from benchmark.phase_split import op_names  # noqa: E402
from benchmark.reference import laguna as reference  # noqa: E402
from horovod_tpu.models import (TransformerConfig, config_from_published,  # noqa: E402
                                transformer_init, transformer_logical_axes,
                                transformer_loss)
from horovod_tpu.models import transformer as tfm  # noqa: E402
from test_models_rope import _half_slicing, _rope_of  # noqa: E402

with open(os.path.join(REPO, "benchmark", "configs", "laguna_xs2.json")) as f:
    PUBLISHED = json.load(f)

# The published pattern at a size the CPU takes: 2 kv heads x head_dim 32,
# 6 (full) and 8 (sliding) query heads, window 16, 16 experts of which 4
# are held (experts 4..7), 2 picks, a leading dense layer and one period.
SMALL = dict(
    PUBLISHED, hidden_size=64, head_dim=32, num_key_value_heads=2,
    num_attention_heads_per_layer=[
        6 if h == 48 else 8
        for h in PUBLISHED["num_attention_heads_per_layer"]],
    sliding_window=16, intermediate_size=128, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, num_experts=16,
    num_experts_per_tok=2, vocab_size=128, layers=5, experts=4,
    experts_first=4, vocab=64)
SEQ = 64


def small_config(**changes):
    c = dict(SMALL, **changes)
    return config_from_published(
        c, layers=c["layers"], experts=c["experts"],
        experts_first=c["experts_first"], vocab=c["vocab"],
        router_score=c["router_score"], max_seq=SEQ, dtype=jnp.float32,
        remat=True, loss_chunk=48)


@pytest.fixture(scope="module")
def model():
    cfg = small_config()
    params = jax.jit(lambda k: transformer_init(k, cfg))(
        jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, SEQ), 0, 64)
    want = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, tokens, config=SMALL)))(params)
    return cfg, params, tokens, want


def _worst_leaf(got, want):
    """The largest error of a leaf relative to the leaf's own scale."""
    return max(
        (float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-12)),
         jax.tree_util.keystr(path))
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree.leaves(want)))


@pytest.mark.parametrize("flash", ["off", "on"])
def test_system_and_reference_agree_to_rounding(model, monkeypatch, flash):
    """Loss, and the gradient of every leaf, on the XLA attention path and
    on the flash kernels' (interpret mode: the windowed and the
    full-causal calls, grouped queries folded at head_dim 32)."""
    cfg, params, tokens, (loss_r, grad_r) = model
    monkeypatch.setenv("HVDT_FLASH_ATTENTION", flash)
    loss_s, grad_s = jax.jit(jax.value_and_grad(
        lambda p: transformer_loss(p, tokens, cfg)))(params)
    assert abs(float(loss_s) - float(loss_r)) < 2e-6 * float(loss_r)
    assert jax.tree.structure(grad_s) == jax.tree.structure(grad_r)
    worst, where = _worst_leaf(grad_s, grad_r)
    assert worst < 5e-5, (worst, where)


@pytest.mark.parametrize("wrong", [
    dict(sliding_window=17), dict(sliding_window=15),
    dict(moe_routed_scaling_factor=1.0), dict(router_score="softmax"),
    dict(experts_first=0), dict(gating=False)],
    ids=["window+1", "window-1", "no_scaling_factor", "softmax_router",
         "other_share", "no_gate"])
def test_a_wrong_model_fails_the_comparison(model, wrong):
    _, params, tokens, (loss_r, grad_r) = model
    cfg = small_config(**wrong)
    if "gating" in wrong:               # the tree keeps its wg leaves
        cfg = small_config()
        cfg = TransformerConfig(**{**cfg.__dict__, "out_gate": False})
    loss_s, grad_s = jax.jit(jax.value_and_grad(
        lambda p: transformer_loss(p, tokens, cfg)))(params)
    worst, _ = _worst_leaf({k: v for k, v in grad_s.items()},
                           {k: grad_r[k] for k in grad_s})
    assert (abs(float(loss_s) - float(loss_r)) > 1e-4 * float(loss_r)
            or worst > 1e-2)


@pytest.mark.parametrize("policy", ["full", "dots"])
def test_a_sparse_layers_checkpoint_keeps_its_route(model, policy):
    """Under either policy the layers' checkpoints save what the expert
    layer names: the gradient program sorts and picks in the forward bodies
    alone (a top-k and two sorts for each of the pattern's two kinds of
    sparse layer), and its numbers are the ones the reference was held to."""
    cfg, params, tokens, (loss_r, grad_r) = model
    cfg = TransformerConfig(**{**cfg.__dict__, "remat_policy": policy})
    step = jax.jit(jax.value_and_grad(
        lambda p: transformer_loss(p, tokens, cfg)))
    text = step.lower(params).compile().as_text()
    names = op_names(text)
    sorts = [names[name] for name in moe_route_sorts.sorts(text)]
    assert len(sorts) == 6
    assert not any("transpose(" in n or "rematted" in n for n in sorts)
    loss_s, grad_s = step(params)
    assert abs(float(loss_s) - float(loss_r)) < 2e-6 * float(loss_r)
    worst, where = _worst_leaf(grad_s, grad_r)
    assert worst < 5e-5, (worst, where)


def test_a_dense_layers_checkpoint_saves_what_a_bare_one_does(monkeypatch):
    """No expert layer, no name in the layer: the gradient program under
    ``remat_policy="full"`` is a bare ``jax.checkpoint``'s, instruction for
    instruction."""
    cfg = TransformerConfig(vocab=64, layers=3, d_model=32, heads=2,
                            kv_heads=2, d_ff=64, max_seq=SEQ,
                            dtype=jnp.float32, remat=True)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, SEQ), 0, 64)

    def text():
        compiled = jax.jit(jax.grad(
            lambda p: transformer_loss(p, tokens, cfg))).lower(
                params).compile().as_text()
        # the instructions without where they came from: no metadata, no
        # tables of files and stack frames
        lines = compiled.splitlines()
        first = next(i for i, line in enumerate(lines) if line.endswith("{"))
        return [re.sub(r", metadata=\{[^}]*\}", "", line)
                for line in lines[first:]]

    kept = text()
    checkpoint = jax.checkpoint
    monkeypatch.setattr(jax, "checkpoint",
                        lambda fn, policy=None: checkpoint(fn))
    assert len(kept) > 500 and "checkpoint" not in "".join(kept)
    assert kept == text()


def test_the_published_configuration_cut_to_its_share():
    cfg = config_from_published(
        PUBLISHED, layers=5, experts=32, vocab=12544, max_seq=8192)
    assert cfg.head_dim == 128 and cfg.d_model == 2048
    assert len(cfg.leading) == 1 and cfg.periods == 1
    (full,), runs = cfg.leading, cfg.period_runs
    assert (full.heads, full.kv_heads, full.window, full.sparse,
            full.d_ff) == (48, 8, None, False, 8192)
    assert full.rope.dim == 64 and full.rope.yarn_factor == 64
    # The three sliding layers are ONE run (one scan, one set of Mosaic
    # call sites), the full sparse layer the other.
    assert [(k.heads, k.window, k.sparse, n) for k, n in runs] == [
        (64, 512, True, 3), (48, None, True, 1)]
    assert runs[0][0].rope == tfm.Rope(theta=10000.0)      # plain
    moe = cfg.moe
    assert (moe.held, moe.routed, moe.per_token, moe.scale, moe.d_ff,
            moe.shared_d_ff) == (32, 256, 8, 2.5, 512, 512)
    assert cfg.out_gate and not cfg.tie_head
    shapes = jax.eval_shape(lambda k: transformer_init(k, cfg),
                            jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 691_623_936
    # The whole model, 1 + 39 layers at period 4: what fills no period
    # leads (dense full, 3 sliding), then 9 periods of (full, 3 sliding).
    whole = config_from_published(PUBLISHED)
    assert (len(whole.leading), len(whole.period), whole.periods) == (4, 4, 9)
    assert [n for _, n in whole.period_runs] == [1, 3]
    with pytest.raises(ValueError, match="one whole period"):
        config_from_published(PUBLISHED, layers=4)


def test_logical_axes_follow_the_patterns_tree(model):
    cfg, params, _, _ = model
    axes = transformer_logical_axes(cfg)
    is_axes = lambda x: isinstance(x, tuple)  # noqa: E731
    assert jax.tree.structure(jax.tree.map(lambda _: 0, params)) == \
        jax.tree.structure(jax.tree.map(lambda _: 0, axes, is_leaf=is_axes))
    for leaf, ax in zip(jax.tree.leaves(params),
                        jax.tree.leaves(axes, is_leaf=is_axes)):
        assert leaf.ndim == len(ax)


def test_the_paged_serving_functions_take_uniform_configurations_only(model):
    cfg, params, tokens, _ = model
    for fn, args in (
            (tfm.transformer_decode_paged, (params, None, None, None, None,
                                            None, cfg, 16)),
            (tfm.transformer_prefill_paged, (params, None, None, None, None,
                                             None, None, cfg, 16)),
            (tfm.transformer_prefill_collect, (params, tokens, cfg))):
        with pytest.raises(NotImplementedError, match="uniform"):
            fn(*args)


def test_a_pattern_refuses_the_manual_islands_and_a_ragged_depth():
    kind = tfm.LayerKind(heads=2, kv_heads=2, d_ff=32)
    with pytest.raises(ValueError, match="whole periods"):
        TransformerConfig(layers=3, d_model=32, period=(kind, kind))
    with pytest.raises(ValueError, match="sp = ep = pp"):
        TransformerConfig(layers=2, d_model=32, period=(kind,), sp=2)
    with pytest.raises(ValueError, match="cfg.moe"):
        TransformerConfig(layers=2, d_model=32, period=(
            tfm.LayerKind(heads=2, kv_heads=2, sparse=True),))


def test_head_dim_is_a_field_of_its_own_and_defaults_to_the_quotient():
    assert TransformerConfig(d_model=512, heads=8).head_dim == 64
    cfg = TransformerConfig(vocab=64, layers=1, d_model=48, heads=4,
                            kv_heads=2, head_dim=32, d_ff=64, max_seq=16,
                            dtype=jnp.float32)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    assert params["block"]["wq"].shape == (1, 48, 128)
    assert params["block"]["wo"].shape == (1, 128, 48)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    assert np.isfinite(float(jax.jit(
        lambda p: transformer_loss(p, tokens, cfg))(params)))


def test_yarn_frequencies_are_the_published_formula():
    """inv_freq = interp * ramp + extrap * (1 - ramp) over the 32 pairs
    of the 64 rotated dimensions: extrap = theta^(-2i/64), interp = extrap
    / 64, ramp from low = floor(c(64)) = 5 to high = ceil(c(1)) = 16, c(r)
    = 64 ln(4096 / (2 pi r)) / (2 ln theta)."""
    import math

    rope = config_from_published(PUBLISHED, layers=5, experts=32,
                                 vocab=12544).leading[0].rope
    got = tfm._rope_frequencies(rope, 128)
    theta = 500000.0
    c = lambda r: 64 * math.log(4096 / (2 * math.pi * r)) / (  # noqa: E731
        2 * math.log(theta))
    low, high = math.floor(c(64)), math.ceil(c(1))
    assert (low, high) == (5, 16)
    i = np.arange(32)
    extrap = theta ** (-2.0 * i / 64)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    np.testing.assert_allclose(got, extrap / 64 * ramp + extrap * (1 - ramp),
                               rtol=1e-6)
    assert got[0] == pytest.approx(1.0) and got[31] == pytest.approx(
        theta ** (-62 / 64) / 64, rel=1e-6)
    assert rope.attention_factor == pytest.approx(0.1 * math.log(64) + 1)


def test_partial_rotation_leaves_the_other_dimensions_alone():
    rope = tfm.Rope(theta=500000.0, dim=8, attention_factor=1.25)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 2, 16))
    pos = jnp.arange(6)[None]
    y = _rope_of(x, pos, rope)
    np.testing.assert_array_equal(y[..., 8:], x[..., 8:])
    np.testing.assert_allclose(y[:, 0, :, :8], 1.25 * x[:, 0, :, :8],
                               rtol=1e-6)      # position 0: cos 1, sin 0
    plain = _rope_of(x, pos, tfm.Rope(theta=123.0))
    np.testing.assert_array_equal(
        plain, _half_slicing(x, pos, tfm.Rope(theta=123.0)))
