"""granite-4.0-h-micro's model through ``TransformerConfig`` (a
``StateSpaceMixer`` layer kind, attention without a position term, the four
multipliers, a tied head) and ``transformer_loss`` at a small size on the
CPU: what ``config_from_published`` makes of the source's keys and what it
refuses; loss and every gradient leaf against the plain reference
(``benchmark/reference/granite_hybrid.py``); each multiplier and the
missing rotary show in the gradients; at their defaults the four constants
are no operation of the program."""

import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.reference import granite_hybrid as reference  # noqa: E402
from horovod_tpu.models import (LayerKind, StateSpaceMixer,  # noqa: E402
                                TransformerConfig, config_from_published,
                                transformer_flops_per_token,
                                transformer_init, transformer_loss)

with open(os.path.join(REPO, "benchmark", "configs",
                       "granite_4_0_h_micro.json")) as f:
    PUBLISHED = json.load(f)

# The published period at a size the CPU takes: ten layers (five Mamba-2,
# one attention, four Mamba-2), 8 heads of 8 over a state of 16 in chunks of
# 16 (four chunks a sequence), attention 4 / 2 heads of 16.
SMALL = dict(PUBLISHED, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=2, shared_intermediate_size=96,
             intermediate_size=96, mamba_n_heads=8, mamba_d_head=8,
             mamba_d_state=16, mamba_chunk_size=16, vocab_size=512,
             vocab=128, attention_multiplier=0.0625)
SEQ = 64


def small_config(published=SMALL, **fields):
    fields = dict(dict(max_seq=SEQ, dtype=jnp.float32, remat=True,
                       loss_chunk=96), **fields)
    return config_from_published(published, layers=published["layers"],
                                 vocab=published["vocab"], **fields)


def tokens_of(samples=2, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (samples, SEQ), 0,
                              SMALL["vocab"])


def gradients_of(published=SMALL, loss=None):
    """The gradient tree of the system's loss (or of ``loss``) under
    ``published`` at the small configuration's seeded weights."""
    cfg = small_config(published)
    params = transformer_init(jax.random.PRNGKey(0), small_config())
    loss = loss or (lambda p, tokens: transformer_loss(p, tokens, cfg))
    return jax.jit(jax.grad(lambda p: loss(p, tokens_of())))(params)


@functools.cache
def base_gradients():
    return gradients_of()


def distance(a, b) -> float:
    """|a - b| / |b| over two trees."""
    a, b = (np.concatenate([np.ravel(x) for x in jax.tree.leaves(t)])
            for t in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def test_config_from_published_reads_the_sources_keys():
    cfg = config_from_published(PUBLISHED, layers=10, vocab=12544,
                                max_seq=8192)
    assert (cfg.d_model, cfg.head_dim, cfg.layers, cfg.vocab) == (
        2048, 64, 10, 12544)
    assert cfg.tie_head and cfg.norm_eps == 1e-5
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (
        12.0, 0.22, 0.015625, 8.0)
    # lead 0, period 10, the attention layer sixth
    assert cfg.leading == () and len(cfg.period) == 10 and cfg.periods == 1
    mamba = LayerKind(heads=0, kv_heads=0, d_ff=8192, ssm=StateSpaceMixer(
        heads=64, head_dim=64, state=128, groups=1, conv=4, conv_bias=True,
        chunk=256))
    attention = LayerKind(heads=32, kv_heads=8, d_ff=8192, rope=None)
    assert list(cfg.period) == [mamba] * 5 + [attention] + [mamba] * 4
    assert [n for _, n in cfg.period_runs] == [5, 1, 4]
    shapes = jax.eval_shape(lambda k: transformer_init(k, cfg),
                            jax.random.PRNGKey(0))
    first = shapes["period"]["0"]
    assert first["w_in"].shape == (1, 5, 2048, 4096 + 4352 + 64)
    assert first["conv"].shape == (1, 5, 4, 4352)
    assert first["conv_bias"].shape == (1, 5, 4352)
    assert first["a_log"].shape == first["d_skip"].shape == (1, 5, 64)
    assert first["ssd_norm"].shape == (1, 5, 4096)
    assert first["w_out"].shape == (1, 5, 4096, 2048)
    assert shapes["period"]["1"]["wq"].shape == (1, 1, 2048, 2048)
    assert shapes["period"]["1"]["wk"].shape == (1, 1, 2048, 512)
    assert "head" not in shapes and shapes["embed"].shape == (12544, 2048)
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 772_160_448 \
        == PUBLISHED["parameters"]
    # the whole model: four periods, every row of the vocabulary
    whole = config_from_published(PUBLISHED, max_seq=8192)
    assert (whole.layers, whole.periods, whole.vocab) == (40, 4, 100352)
    # a configuration without the keys is what it was
    plain = {k: v for k, v in SMALL.items() if k not in (
        "position_embedding_type", "embedding_multiplier",
        "residual_multiplier", "attention_multiplier", "logits_scaling")}
    cfg = config_from_published(dict(plain, layer_types=["attention"] * 40),
                                layers=2)
    assert cfg.period[0].rope is not None
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (1, 1, 0, 1)


def test_the_mixers_leaves_start_as_the_published_code_starts_them():
    params = transformer_init(jax.random.PRNGKey(0), small_config())
    run = params["period"]["0"]
    np.testing.assert_allclose(run["a_log"][0, 0],
                               np.log(np.arange(1, 9)), rtol=1e-6)
    for name in ("d_skip", "ssd_norm"):
        assert np.all(np.asarray(run[name]) == 1.0), name
    # a time step drawn log-uniform in [1e-3, 1e-1], each head and layer
    # its own
    step = np.log1p(np.exp(np.asarray(run["dt_bias"], np.float64)))
    assert 1e-3 * 0.999 <= step.min() < step.max() <= 1e-1 * 1.001
    assert len(np.unique(step)) == step.size
    for name in ("conv", "conv_bias"):
        leaf = np.asarray(run[name])
        assert np.abs(leaf).max() <= 0.5 and leaf.std() > 0.2, name


@pytest.mark.parametrize("key, value, match", [
    ("layer_types", ["mamba", "hyena"] * 20, "layer_types holds"),
    ("position_embedding_type", "alibi", "position_embedding_type"),
    ("num_local_experts", 8, "num_local_experts"),
    ("mamba_proj_bias", True, "mamba_proj_bias")])
def test_what_the_configuration_refuses(key, value, match):
    with pytest.raises(ValueError, match=match):
        config_from_published(dict(SMALL, **{key: value}), layers=10)


def test_a_recurrent_mixer_is_refused_under_diffusion_over_blocks():
    with pytest.raises(ValueError, match="diffusion over blocks"):
        TransformerConfig(layers=1, diffusion_block=4, period=(LayerKind(
            heads=0, kv_heads=0, d_ff=8,
            ssm=StateSpaceMixer(heads=2, head_dim=4, state=4)),))


@pytest.mark.parametrize("path, loss_chunk", [("off", 0), ("off", 96),
                                              ("on", 96)],
                         ids=["xla-dense", "xla-chunked", "kernels-chunked"])
def test_loss_and_gradients_match_the_plain_reference(monkeypatch, path,
                                                      loss_chunk):
    monkeypatch.setenv("HVDT_FLASH_ATTENTION", path)
    cfg = small_config(loss_chunk=loss_chunk)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    tokens = tokens_of()
    got, g_got = jax.jit(jax.value_and_grad(
        lambda p: transformer_loss(p, tokens, cfg)))(params)
    want, g_want = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, tokens, config=SMALL)))(params)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    leaves = jax.tree_util.tree_leaves_with_path(g_got)
    assert len(leaves) == 2 + 2 * 13 + 9
    for (path_, a), b in zip(leaves, jax.tree.leaves(g_want)):
        assert np.abs(b).max() > 0, jax.tree_util.keystr(path_)
        np.testing.assert_allclose(a, b, atol=2e-6 + 2e-4 * np.abs(b).max(),
                                   err_msg=jax.tree_util.keystr(path_))


@pytest.mark.parametrize("key, value", [
    ("embedding_multiplier", 6), ("residual_multiplier", 1.0),
    ("attention_multiplier", 0.25), ("logits_scaling", 4)])
def test_each_multiplier_shows_as_the_reference_has_it(key, value):
    """At seeded weights the loss hardly moves with a constant; the
    gradients do, and move as the reference's."""
    perturbed = dict(SMALL, **{key: value})
    got = gradients_of(perturbed)
    want = gradients_of(perturbed, lambda p, tokens: reference.loss(
        p, tokens, config=perturbed))
    assert distance(got, want) < 1e-4
    assert distance(got, base_gradients()) > 0.05


def test_a_rotary_embedding_would_show():
    rotary = gradients_of(dict(SMALL, position_embedding_type="rope"))
    attention = lambda g: g["period"]["1"]  # noqa: E731
    assert distance(attention(rotary), attention(base_gradients())) > 0.05


def equations(jaxpr) -> int:
    """The equations of a jaxpr and of every jaxpr inside it."""
    total = 0
    for eqn in jaxpr.eqns:
        total += 1
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [
                    value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    total += equations(inner)
    return total


def test_at_their_defaults_the_constants_are_no_operation():
    """A uniform configuration (the dense LM cells') traces to the same
    number of equations whether the four fields exist or not: set, they
    add the embedding's product, two products a layer body and the logits'
    division; the score scale is an argument of the attention call."""
    cfg = TransformerConfig(vocab=64, layers=2, d_model=32, heads=4,
                            kv_heads=4, d_ff=64, max_seq=16,
                            dtype=jnp.float32)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((1, 16), jnp.int32)

    def count(cfg):
        return equations(jax.make_jaxpr(
            lambda p: transformer_loss(p, tokens, cfg))(params).jaxpr)

    assert count(dataclasses.replace(
        cfg, embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.0625, logits_scaling=8.0)) == count(cfg) + 4
    for field in ("embedding_multiplier", "logits_scaling"):
        assert count(dataclasses.replace(cfg, **{field: 2.0})) \
            == count(cfg) + 1, field


def test_the_operations_count_takes_the_state_space_mixer():
    cfg = config_from_published(PUBLISHED, layers=10, vocab=12544,
                                max_seq=8192)
    mamba = 2 * (2048 * 8512 + 4 * 4352 + 4096 * 2048 + 2_129_920
                 + 3 * 2048 * 8192)
    attention = 2 * (2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 8192 * 2048
                     + 3 * 2048 * 8192)
    assert transformer_flops_per_token(cfg) == pytest.approx(
        9 * mamba + attention + 2 * 2048 * 12544)
