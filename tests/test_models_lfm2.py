"""LFM2-24B-A2B's model through ``TransformerConfig`` (a ``ShortConv`` layer
kind, GQA with q / k norm, leading dense layers before sparse ones, a router
that picks by score plus a selection bias and weighs by the score, a held
range of layers that need not start at layer 0) and ``transformer_loss`` at
a small size on the CPU: what ``config_from_published`` makes of the
source's keys; loss and every gradient leaf against the plain reference
(``benchmark/reference/lfm2.py``) with an untied random router and a random
bias; the four expert shares of a sparse layer add up to the uncut layer;
``layers_first`` 0 builds every accepted configuration's tree as it was."""

import dataclasses
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.reference import lfm2 as reference  # noqa: E402
from horovod_tpu.models import (Experts, LayerKind, Rope,  # noqa: E402
                                ShortConv, TransformerConfig,
                                config_from_published,
                                transformer_flops_per_token,
                                transformer_init, transformer_loss)
from horovod_tpu.models import transformer as tfm  # noqa: E402

CONFIGS = os.path.join(REPO, "benchmark", "configs")
with open(os.path.join(CONFIGS, "lfm2_24b_a2b.json")) as f:
    PUBLISHED = json.load(f)

# The held range at a size the CPU takes: layer 1 (conv + dense) and the
# period (attention, conv, conv, conv), all sparse; 4 / 2 heads of 16, 8
# experts of 32 (4 held), top 2.
SMALL = dict(PUBLISHED, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=2, intermediate_size=96,
             moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
             experts=4, vocab_size=512, vocab=128)
SEQ = 32
FAMILY = dict(qk_norm=True, router_score="sigmoid", normalize_eps=1e-6)


def small_config(published=SMALL, **fields):
    fields = dict(dict(max_seq=SEQ, dtype=jnp.float32, remat=True,
                       loss_chunk=96, **FAMILY), **fields)
    return config_from_published(
        published, layers=published["layers"],
        layers_first=published["layers_first"],
        experts=published["experts"],
        experts_first=published["experts_first"], vocab=published["vocab"],
        **fields)


def seeded(cfg, seed=0, bias=0.3):
    """The weights with a bias that is not the constructor's zeros (the
    router is the drawn one: untied)."""
    params = transformer_init(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 8))
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: bias * jax.random.normal(
            next(keys), leaf.shape, leaf.dtype)
        if path[-1].key == "router_bias" else leaf, params)


def tokens_of(samples=2, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (samples, SEQ), 0,
                              SMALL["vocab"])


def test_config_from_published_reads_the_sources_keys():
    cfg = config_from_published(PUBLISHED, layers=5, layers_first=1,
                                experts=16, vocab=8192, max_seq=8192,
                                **FAMILY)
    assert (cfg.d_model, cfg.head_dim, cfg.layers, cfg.vocab) == (
        2048, 64, 5, 8192)
    assert cfg.tie_head and cfg.norm_eps == 1e-5 and cfg.qk_norm
    conv = ShortConv(taps=3, bias=False)
    rope = Rope(theta=1e6)
    assert list(cfg.leading) == [
        LayerKind(heads=0, kv_heads=0, d_ff=11776, conv=conv)]
    assert list(cfg.period) == [
        LayerKind(heads=32, kv_heads=8, sparse=True, rope=rope)] + [
        LayerKind(heads=0, kv_heads=0, sparse=True, conv=conv)] * 3
    assert cfg.periods == 1 and [n for _, n in cfg.period_runs] == [1, 3]
    assert cfg.moe == Experts(
        held=16, d_ff=1536, routed=64, per_token=4, first=0,
        score="sigmoid", normalize=True, scale=1.0, select_bias=True,
        normalize_eps=1e-6)
    shapes = jax.eval_shape(lambda k: transformer_init(k, cfg),
                            jax.random.PRNGKey(0))
    lead, attn, convs = (shapes["lead"]["0"], shapes["period"]["0"],
                         shapes["period"]["1"])
    assert lead["w_in"].shape == (2048, 6144)
    assert lead["conv"].shape == (3, 2048) and "conv_bias" not in lead
    assert lead["w_out"].shape == (2048, 2048)
    assert lead["w_up"].shape == (2048, 11776) and "w_router" not in lead
    assert attn["wq"].shape == (1, 1, 2048, 2048)
    assert attn["wk"].shape == (1, 1, 2048, 512)
    assert attn["q_norm"].shape == attn["k_norm"].shape == (1, 1, 64)
    assert convs["w_in"].shape == (1, 3, 2048, 6144)
    assert convs["w_router"].shape == (1, 3, 2048, 64)
    assert convs["router_bias"].shape == (1, 3, 64)
    assert convs["router_bias"].dtype == jnp.float32
    assert convs["w_up"].shape == (1, 3, 16, 2048, 1536)
    assert "head" not in shapes and shapes["embed"].shape == (8192, 2048)
    # the issue's table
    def count(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    assert count(lead) == 89_139_200
    assert count(attn) == 161_616_064
    assert count(convs) == 503_740_608
    assert count(shapes) == 771_275_136 == PUBLISHED["parameters"]


def test_the_whole_model_counts_24b_and_two_leading_dense_layers():
    whole = config_from_published(PUBLISHED, max_seq=8192, **FAMILY)
    assert (whole.layers, whole.vocab, whole.moe.held) == (40, 65536, 64)
    # 2 dense + 38 at period 4: what does not fill a period leads
    assert len(whole.leading) == 4 and whole.periods == 9
    assert [k.sparse for k in whole.leading] == [False, False, True, True]
    assert [k.conv is None for k in whole.leading + whole.period] == [
        False, False, True, False, False, False, True, False]
    shapes = jax.eval_shape(lambda k: transformer_init(k, whole),
                            jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 23_843_661_440
    # a range past the leading layers holds none of them
    later = config_from_published(PUBLISHED, layers=8, layers_first=6,
                                  experts=16, vocab=8192, **FAMILY)
    assert later.leading == () and later.periods == 2
    assert later.period[0].conv is None and later.period[1].conv
    for first, layers in ((-1, 5), (36, 5), (1, 3)):
        with pytest.raises(ValueError):
            config_from_published(PUBLISHED, layers=layers,
                                  layers_first=first, **FAMILY)


def test_the_convs_taps_start_as_torchs_conv1d_and_the_bias_at_zero():
    params = transformer_init(jax.random.PRNGKey(0), small_config())
    taps = np.asarray(params["period"]["1"]["conv"])
    assert np.abs(taps).max() <= 3 ** -0.5 and np.abs(taps).max() > 0.5
    assert abs(taps.mean()) < 0.05
    for run in ("0", "1"):
        assert not np.asarray(params["period"][run]["router_bias"]).any()


def test_a_short_convolution_is_refused_under_diffusion_over_blocks():
    with pytest.raises(ValueError, match="recurrent"):
        small_config(diffusion_block=4)
    with pytest.raises(ValueError, match="layer_types holds"):
        config_from_published(dict(SMALL, layer_types=["convolution"] * 40))


@pytest.mark.parametrize("path, loss_chunk", [("off", 0), ("off", 96),
                                              ("on", 96)],
                         ids=["xla-dense", "xla-chunked", "kernels-chunked"])
def test_loss_and_gradients_match_the_plain_reference(monkeypatch, path,
                                                      loss_chunk):
    """An UNTIED random router and a random bias: rows pick different
    experts, several or none of them held."""
    monkeypatch.setenv("HVDT_FLASH_ATTENTION", path)
    cfg = small_config(loss_chunk=loss_chunk)
    params = seeded(cfg)
    tokens = tokens_of()
    got, g_got = jax.jit(jax.value_and_grad(
        lambda p: transformer_loss(p, tokens, cfg)))(params)
    want, g_want = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, tokens, config=SMALL)))(params)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    leaves = jax.tree_util.tree_leaves_with_path(g_got)
    assert len(leaves) == 2 + 8 + 13 + 10
    for (path_, a), b in zip(leaves, jax.tree.leaves(g_want)):
        name = jax.tree_util.keystr(path_)
        if "router_bias" in name:
            assert not np.asarray(a).any() and not np.asarray(b).any()
            continue
        assert np.abs(b).max() > 0, name
        np.testing.assert_allclose(a, b, atol=2e-6 + 2e-4 * np.abs(b).max(),
                                   err_msg=name)


@pytest.mark.parametrize("wrong", [
    dict(use_expert_bias=False), dict(conv_L_cache="last"),
    dict(norm_eps=1e-2), dict(num_dense_layers=0)],
    ids=["picks_by_the_score_alone", "the_conv_cut_to_its_last_tap",
         "another_epsilon", "no_leading_dense_layer"])
def test_a_reference_of_another_model_disagrees(wrong):
    cfg = small_config()
    params = seeded(cfg)
    tokens = tokens_of()
    if wrong.get("conv_L_cache") == "last":
        # v_t = w[2] u_t: the taps that read the past zeroed
        wrong, params = {}, jax.tree_util.tree_map_with_path(
            lambda path, leaf: leaf.at[..., :2, :].set(0.0)
            if path[-1].key == "conv" else leaf, params)
        other = jax.jit(jax.grad(lambda p: reference.loss(
            p, tokens, config=SMALL)))(params)
        params = seeded(cfg)
    elif "num_dense_layers" in wrong:
        with pytest.raises(KeyError):       # layer 1 has no router
            reference.loss(params, tokens, config=dict(SMALL, **wrong))
        return
    else:
        other = jax.jit(jax.grad(lambda p: reference.loss(
            p, tokens, config=dict(SMALL, **wrong))))(params)
    ours = jax.jit(jax.grad(
        lambda p: transformer_loss(p, tokens, cfg)))(params)
    a, b = (np.concatenate([np.ravel(x) for x in jax.tree.leaves(t)])
            for t in (ours, other))
    assert np.linalg.norm(a - b) / np.linalg.norm(b) > 1e-2


@pytest.mark.parametrize("kind", ["attention", "conv"])
def test_the_four_shares_of_a_sparse_layer_add_up_to_the_uncut_layer(kind):
    """One sparse layer of the stack (layer 2, attention, or layer 3,
    conv) on ONE set of weights for all 8 experts: the feed-forward's part
    that each share of 2 experts computes (``experts_first`` 0, 2, 4, 6),
    summed, is what the uncut reference layer adds; the mixer, which every
    share computes alike, counted once."""
    index = 2 if kind == "attention" else 3
    uncut = dict(SMALL, experts=8, experts_first=0, layers=4,
                 layers_first=index)
    whole = small_config(uncut)
    run = "0" if kind == "attention" else \
        str([k.conv is not None for k, _ in whole.period_runs].index(True))
    position = 0
    params = seeded(whole, seed=3)
    p = jax.tree.map(lambda a: a[0, position], params["period"][run])
    x = jax.random.normal(jax.random.PRNGKey(5), (SEQ, 64))
    with jax.default_matmul_precision("highest"):
        want = reference.layer(x, p, index, uncut) - x
        mixer = {"conv": reference.short_conv,
                 "attention": reference.attention}[kind]
        mixed = mixer(reference.norm(x, p["ln1"], 1e-5), p, uncut)

        def share(first):
            held = dict(p, **{n: p[n][first:first + 2]
                              for n in ("w_up", "w_gate", "w_down")})
            cfg = dataclasses.replace(whole, moe=dataclasses.replace(
                whole.moe, held=2, first=first))
            kind_ = whole.period[0 if kind == "attention" else 1]
            positions = jnp.arange(SEQ)[None]
            out = tfm._block(held, x[None], positions, cfg, kind_)[0]
            return out - x - mixed

        shares = [share(first) for first in (0, 2, 4, 6)]
    np.testing.assert_allclose(sum(shares) + mixed, want, atol=3e-5)
    assert np.abs(np.asarray(shares[0] + mixed - want)).max() > 1e-2


ACCEPTED = {
    "laguna_xs2": dict(keys=("layers", "experts", "experts_first", "vocab"),
                       fields=dict(out_gate="head")),
    "qwen3_next_80b": dict(
        keys=("layers", "experts", "experts_first", "vocab"),
        fields=dict(shared_gate=True, out_gate="elementwise", qk_norm=True,
                    zero_centered_norm=True)),
    "sdar_30b_a3b": dict(
        keys=("layers", "experts", "experts_first", "vocab"),
        fields=dict(router_score="softmax", qk_norm=True,
                    diffusion_block=4)),
    "evabyte": dict(keys=("layers", "heads", "heads_first"), fields={}),
    "granite_4_0_h_micro": dict(keys=("layers", "vocab"), fields={}),
}


# (parameters, md5 of every leaf's path, shape and dtype), recorded on the
# commit before ``layers_first`` existed (f58c984) by the loop below.
TREES = {
    "evabyte": (620_015_616, "9f66772cc08d"),
    "granite_4_0_h_micro": (772_160_448, "3e9a7a486ada"),
    "laguna_xs2": (691_623_936, "e31d4f310140"),
    "qwen3_next_80b": (625_667_136, "dc5153921cc3"),
    "sdar_30b_a3b": (645_623_296, "4ba3c0363fd5"),
}


@pytest.mark.parametrize("name", sorted(ACCEPTED))
def test_layers_first_0_builds_every_accepted_configurations_tree(name):
    """Leaf for leaf: the same configuration object, so the same kinds,
    the same keys and the same draws."""
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        published = json.load(f)
    cut = {k: published[k] for k in ACCEPTED[name]["keys"]}
    fields = ACCEPTED[name]["fields"]
    plain = config_from_published(published, **cut, **fields)
    explicit = config_from_published(published, layers_first=0, **cut,
                                     **fields)
    assert plain == explicit
    assert plain.moe is None or (not plain.moe.select_bias
                                 and plain.moe.normalize_eps == 0.0)
    assert all(k.conv is None for k in plain.leading + plain.period)
    shapes = jax.eval_shape(lambda k: transformer_init(k, plain),
                            jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves_with_path(shapes)
    text = "\n".join(f"{jax.tree_util.keystr(path)} {leaf.shape} "
                     f"{leaf.dtype}" for path, leaf in leaves)
    assert (sum(leaf.size for _, leaf in leaves),
            hashlib.md5(text.encode()).hexdigest()[:12]) == TREES[name]
    assert "router_bias" not in str(jax.tree.structure(shapes))


def test_the_operations_count_takes_the_short_convolution():
    cfg = small_config()
    d = 64
    conv = 2 * (4 * d * d + 3 * d)
    attention = 2 * d * (64 + 2 * 32 + 64) + 2 * 2 * SEQ * 64
    sparse = 2 * d * 8 + 2 * d * 32 * 3 * 2 * 4 / 8
    assert transformer_flops_per_token(cfg) == (
        conv + 2 * d * 96 * 3 + attention + sparse + 3 * (conv + sparse)
        + 2 * d * 128)
