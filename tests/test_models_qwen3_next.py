"""The hybrid pattern of Qwen3-Next (three Gated DeltaNet layers to one
gated full-attention layer, q/k norm, an elementwise output gate from the
query projection, zero-centred norms, a softmax router over all experts
with a gated shared expert) through ``TransformerConfig`` against the
plain reference ``benchmark/reference/qwen3_next.py`` in float32: the same
model to rounding on the XLA attention path and, through the interpreter,
on the flash kernels' at head_dim 256 with groups of 8; what
``config_from_published`` makes of the catalog's keys; and the uniform and
the Laguna configurations' trees and losses as they were."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.reference import qwen3_next as reference  # noqa: E402
from horovod_tpu.models import (TransformerConfig, config_from_published,  # noqa: E402
                                transformer_flops_per_token,
                                transformer_init, transformer_logical_axes,
                                transformer_loss)
from horovod_tpu.models import transformer as tfm  # noqa: E402

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
with open(os.path.join(REPO, "benchmark", "configs",
                       "qwen3_next_80b.json")) as f:
    PUBLISHED = json.load(f)

# The published pattern at a size the CPU takes: one period, 2 key and 4
# value heads of 16 in the linear layers, 4 query heads over 2 of 32 in the
# full one, 16 experts of which 4 are held (experts 4..7), 3 picks.
SMALL = dict(
    PUBLISHED, hidden_size=64, head_dim=32, num_attention_heads=4,
    num_key_value_heads=2, num_attention_heads_per_layer=[4] * 48,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, num_experts=16,
    num_experts_per_tok=3, vocab_size=128, layers=4, experts=4,
    experts_first=4, vocab=64)
# The full layer at the published head: 8 query heads on 1 kv head of 256.
WIDE_HEAD = dict(SMALL, head_dim=256, num_attention_heads=8,
                 num_key_value_heads=1,
                 num_attention_heads_per_layer=[8] * 48)
SEQ = 128


def small_config(published=SMALL, **changes):
    c = dict(published, **changes)
    return config_from_published(
        c, layers=c["layers"], experts=c["experts"],
        experts_first=c["experts_first"], vocab=c["vocab"],
        router_score=c["router_score"], shared_gate=c["shared_expert_gate"],
        out_gate=c["attn_output_gate"], qk_norm=c["qk_norm"],
        zero_centered_norm=c["zero_centered_norm"], max_seq=SEQ,
        dtype=jnp.float32, remat=True, loss_chunk=48)


def _model(published):
    cfg = small_config(published)
    params = jax.jit(lambda k: transformer_init(k, cfg))(
        jax.random.PRNGKey(0))
    # the gains of the zero-centred norms start at 0: move them, so that a
    # model that scaled by the gain alone would be told apart
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.1 if path[-1].key in (
            "ln1", "ln2", "ln_f", "q_norm", "k_norm") else x, params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, SEQ), 0, 64)
    want = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, tokens, config=published)))(params)
    return cfg, params, tokens, want


@pytest.fixture(scope="module")
def model():
    return _model(SMALL)


def _worst_leaf(got, want):
    """The largest error of a leaf relative to the leaf's own scale."""
    return max(
        (float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-12)),
         jax.tree_util.keystr(path))
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree.leaves(want)))


def _agree(cfg, params, tokens, want):
    loss_r, grad_r = want
    loss_s, grad_s = jax.jit(jax.value_and_grad(
        lambda p: transformer_loss(p, tokens, cfg)))(params)
    assert abs(float(loss_s) - float(loss_r)) < 2e-6 * float(loss_r)
    assert jax.tree.structure(grad_s) == jax.tree.structure(grad_r)
    # a leaf of a few numbers (dt_bias, a_log) sums every token's share in
    # another order than the recurrence does: 3e-4 of its scale
    worst, where = _worst_leaf(grad_s, grad_r)
    assert worst < 1e-3, (worst, where)


def test_system_and_reference_agree_to_rounding(model, monkeypatch):
    """Loss, and the gradient of every leaf, on the XLA attention path:
    the chunked scan against the reference's token-by-token recurrence, two
    derivations of one rule."""
    monkeypatch.setenv("HVDT_FLASH_ATTENTION", "off")
    _agree(*model)


def test_the_kernel_path_at_head_dim_256_with_groups_of_8(monkeypatch):
    """The flash kernels through the interpreter at the published head: a
    block is one head of 256 lanes, eight query heads pick the one kv head
    in the index map, dk and dv are summed over the group by XLA."""
    from horovod_tpu.ops import pallas_kernels as pk

    assert pk._heads_per_program(8, 1, 256) == 1
    monkeypatch.setenv("HVDT_FLASH_ATTENTION", "on")
    _agree(*_model(WIDE_HEAD))


@pytest.mark.parametrize("wrong", [
    dict(router_score="sigmoid"), dict(norm_topk_prob=False),
    dict(shared_expert_gate=False), dict(qk_norm=False),
    dict(zero_centered_norm=False), dict(partial_rotary_factor=0.5),
    dict(experts_first=0)],
    ids=["sigmoid_router", "picks_not_normalised", "no_shared_gate",
         "no_qk_norm", "norms_scale_by_the_gain", "half_the_head_rotates",
         "other_share"])
def test_a_wrong_model_fails_the_comparison(model, wrong):
    _, params, tokens, (loss_r, grad_r) = model
    cfg = small_config(**wrong)
    loss_s, grad_s = jax.jit(jax.value_and_grad(
        lambda p: transformer_loss(p, tokens, cfg)))(params)
    worst, _ = _worst_leaf(grad_s, grad_r)
    assert (abs(float(loss_s) - float(loss_r)) > 1e-4 * float(loss_r)
            or worst > 1e-2)


def test_the_catalogs_keys_give_the_period_and_the_issues_counts():
    """From the catalog's ``config`` alone (no derived ``layer_types``):
    48 layers of period (linear, linear, linear, full), no leading layer;
    cut to the share, the parameter counts of ISSUE 33."""
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        row = [r for r in map(json.loads, filter(str.strip, f))
               if r["name"] == "Qwen3-Next-80B-A3B-Instruct"][0]
    modelling = dict(router_score="softmax", shared_gate=True,
                     out_gate="elementwise", qk_norm=True,
                     zero_centered_norm=True)
    whole = config_from_published(row["config"], **modelling)
    assert (whole.layers, len(whole.leading), whole.periods) == (48, 0, 12)
    assert [k.linear is not None for k in whole.period] == [
        True, True, True, False]
    assert whole == config_from_published(
        {k: v for k, v in PUBLISHED.items()}, **modelling)
    cfg = config_from_published(row["config"], layers=4, experts=32,
                                vocab=18992, max_seq=16384, **modelling)
    (linear, n_linear), (full, n_full) = cfg.period_runs
    assert (n_linear, n_full) == (3, 1)
    assert linear.linear == tfm.LinearMixer(
        key_heads=16, value_heads=32, key_dim=128, value_dim=128, conv=4)
    assert (full.heads, full.kv_heads, full.window, full.linear) == (
        16, 2, None, None)
    assert (full.rope.theta, full.rope.dim) == (1e7, 64)
    assert cfg.head_dim == 256 and linear.sparse and full.sparse
    moe = cfg.moe
    assert (moe.held, moe.routed, moe.per_token, moe.score, moe.normalize,
            moe.scale, moe.d_ff, moe.shared_d_ff, moe.shared_gate) == (
        32, 512, 10, "softmax", True, 1.0, 512, 512, True)
    assert (cfg.out_gate, cfg.qk_norm, cfg.zero_centered_norm,
            cfg.tie_head) == ("elementwise", True, True, False)
    shapes = jax.eval_shape(lambda k: transformer_init(k, cfg),
                            jax.random.PRNGKey(0))

    def count(run, names):
        return sum(shapes["period"][run][n].size for n in names)

    mixer = ("w_qkvz", "w_ba", "conv", "a_log", "dt_bias", "gdn_norm",
             "w_out")
    assert count("0", mixer) == 3 * 33_718_464
    assert count("1", ("wq", "wk", "wv", "wo", "q_norm", "k_norm")) \
        == 27_263_488
    for run, layers in (("0", 3), ("1", 1)):
        assert count(run, ("w_router", "ws_up", "ws_gate", "ws_down",
                           "ws_sg")) == layers * 4_196_352
        assert count(run, ("w_up", "w_gate", "w_down")) \
            == layers * 32 * 3_145_728
    assert shapes["embed"].size + shapes["head"].size == 77_791_232
    total = sum(x.size for x in jax.tree.leaves(shapes))
    # the issue's 625.7M, and the 9 norm gains of 2048 it leaves out
    assert total == 625_648_704 + 9 * 2048 == 625_667_136
    with pytest.raises(ValueError, match="one whole period"):
        config_from_published(row["config"], layers=3, **modelling)


def test_initial_values_are_the_published_models(model):
    cfg, _, _, _ = model
    p = transformer_init(jax.random.PRNGKey(3), cfg)
    run = p["period"]["0"]
    assert float(jnp.abs(run["ln1"]).max()) == 0.0 == float(
        jnp.abs(p["ln_f"]).max())
    assert float(jnp.abs(p["period"]["1"]["q_norm"]).max()) == 0.0
    assert bool((run["dt_bias"] == 1).all() & (run["gdn_norm"] == 1).all())
    a = jnp.exp(run["a_log"])
    assert 1e-3 <= float(a.min()) and float(a.max()) < 16.0
    assert run["conv"].shape[-2:] == (4, 2 * 32 + 64)
    # a per-head gate and an elementwise one are two values of one field
    assert "wg" not in p["period"]["1"]
    assert p["period"]["1"]["wq"].shape[-1] == 4 * 32 * 2


def test_logical_axes_and_flops_cover_the_new_leaves(model):
    cfg, params, _, _ = model
    axes = transformer_logical_axes(cfg)
    is_axes = lambda x: isinstance(x, tuple)  # noqa: E731
    assert jax.tree.structure(jax.tree.map(lambda _: 0, params)) == \
        jax.tree.structure(jax.tree.map(lambda _: 0, axes, is_leaf=is_axes))
    for leaf, ax in zip(jax.tree.leaves(params),
                        jax.tree.leaves(axes, is_leaf=is_axes)):
        assert leaf.ndim == len(ax)
    # forward operations a token at the cell's sizes, by the model's own
    # count (the full score square, as it always counted): each part of
    # a linear layer is there
    big = config_from_published(
        PUBLISHED, layers=4, experts=32, vocab=18992, max_seq=16384,
        router_score="softmax", shared_gate=True, out_gate="elementwise",
        qk_norm=True, zero_centered_norm=True)
    d, scan = 2048, tfm.scan_macs_per_token(
        key_heads=16, value_heads=32, key_dim=128, value_dim=128)
    linear = 2 * (d * (12288 + 64) + 4 * 8192 + 4096 * d + scan)
    full = 2 * d * (16 * 256 * 3 + 2 * 2 * 256) + 4 * 16384 * 16 * 256
    feed_forward = 2 * (d * 512 + 3 * d * 512 * (10 * 32 / 512 + 1) + d)
    assert transformer_flops_per_token(big) == pytest.approx(
        3 * linear + full + 4 * feed_forward + 2 * d * 18992, rel=1e-12)


def test_the_output_gate_is_one_field_with_two_forms():
    kind = tfm.LayerKind(heads=2, kv_heads=2, d_ff=32)
    base = dict(layers=1, d_model=32, period=(kind,))
    assert TransformerConfig(**base).out_gate == ""
    assert TransformerConfig(out_gate=True, **base).out_gate == "head"
    assert TransformerConfig(out_gate=False, **base).out_gate == ""
    assert TransformerConfig(out_gate="elementwise",
                             **base).out_gate == "elementwise"
    with pytest.raises(ValueError, match="out_gate"):
        TransformerConfig(out_gate="rows", **base)
    for fn in (tfm.transformer_decode_paged, tfm.transformer_prefill_paged):
        with pytest.raises(NotImplementedError, match="uniform"):
            tfm._uniform_only(TransformerConfig(out_gate="elementwise"),
                              fn.__name__)


# What the parent commit (2286aa7) gave for three configurations from
# before this model, trees and losses, seeds 0 and 1: loss, sum of |leaf|
# over the tree, leaves.
LAGUNA = dict(
    json.load(open(os.path.join(REPO, "benchmark", "configs",
                                "laguna_xs2.json"))),
    hidden_size=64, head_dim=32, num_key_value_heads=2, sliding_window=16,
    intermediate_size=128, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, num_experts=16,
    num_experts_per_tok=2, vocab_size=128)
LAGUNA["num_attention_heads_per_layer"] = [
    6 if h == 48 else 8 for h in LAGUNA["num_attention_heads_per_layer"]]
BEFORE = {
    "laguna": (lambda: config_from_published(
        LAGUNA, layers=5, experts=4, experts_first=4, vocab=64,
        router_score="sigmoid", max_seq=64, dtype=jnp.float32, remat=True,
        loss_chunk=48), 64, 4.185123443603516, 27218.62109375, 41),
    "uniform": (lambda: TransformerConfig(
        vocab=64, layers=2, d_model=32, heads=4, kv_heads=2, d_ff=64,
        max_seq=32, dtype=jnp.float32), 32, 4.1595563888549805,
        2621.15380859375, 11),
    "uniform_moe": (lambda: TransformerConfig(
        vocab=64, layers=2, d_model=32, heads=4, kv_heads=4, d_ff=64,
        max_seq=32, dtype=jnp.float32, num_experts=4), 32,
        4.187695503234863, 5355.80615234375, 11),
}


@pytest.mark.parametrize("name", list(BEFORE))
def test_the_configurations_from_before_keep_their_trees_and_losses(name):
    make, seq, loss, tree, leaves = BEFORE[name]
    cfg = make()
    params = jax.jit(lambda k: transformer_init(k, cfg))(
        jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, seq), 0, 64)
    assert len(jax.tree.leaves(params)) == leaves
    assert float(sum(jnp.abs(x).sum() for x in jax.tree.leaves(params))) \
        == pytest.approx(tree, rel=1e-6)
    assert float(jax.jit(lambda p: transformer_loss(p, tokens, cfg))(
        params)) == pytest.approx(loss, rel=1e-6)
    assert cfg.out_gate in ("", "head")
