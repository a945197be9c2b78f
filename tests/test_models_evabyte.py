"""EvaByte's model through ``TransformerConfig`` (an ``Eva`` layer kind, a
held range of heads, ``pred_heads``) and ``transformer_loss`` at a small
size on the CPU: what ``config_from_published`` makes of the source's keys;
THE SHARES ADD UP (the attention sublayer's output from four held ranges
of heads sums to the uncut reference's); the n-target loss against a loop
over the heads, and one prediction head equal to ``transformer_loss`` as it
was, bit for bit; the operations count at the head's own width; the scopes
the benchmark's readers take."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.reference import evabyte as reference  # noqa: E402
from horovod_tpu.models import (Eva, LayerKind, TransformerConfig,  # noqa: E402
                                config_from_published,
                                transformer_apply,
                                transformer_flops_per_token,
                                transformer_init, transformer_loss)
from horovod_tpu.models import transformer as tm  # noqa: E402

with open(os.path.join(REPO, "benchmark", "configs", "evabyte.json")) as f:
    PUBLISHED = json.load(f)

# The published layer at a size the CPU takes: two layers, 8 heads of 16,
# windows of 8 in chunks of 2, 3 prediction heads over 40 ids.
SMALL = dict(PUBLISHED, hidden_size=128, num_attention_heads=8,
             num_key_value_heads=8, intermediate_size=96, window_size=8,
             chunk_size=2, num_pred_heads=3, vocab_size=40, layers=2)
SEQ = 24


def small_config(heads=None, heads_first=0, **fields):
    fields = dict(dict(max_seq=SEQ, dtype=jnp.float32, remat=True,
                       loss_chunk=16), **fields)
    return config_from_published(SMALL, layers=SMALL["layers"], heads=heads,
                                 heads_first=heads_first, **fields)


def tokens_of(samples=2, seed=1, seq=SEQ):
    return jax.random.randint(jax.random.PRNGKey(seed), (samples, seq), 0,
                              SMALL["vocab_size"])


def test_config_from_published_reads_the_sources_keys():
    cfg = config_from_published(PUBLISHED, layers=4, heads=8, heads_first=0,
                                max_seq=32768)
    assert (cfg.d_model, cfg.head_dim, cfg.layers, cfg.vocab) == (
        4096, 128, 4, 320)
    assert cfg.pred_heads == 8 and not cfg.tie_head
    assert cfg.zero_centered_norm and cfg.norm_eps == 1e-5
    assert cfg.leading == () and len(cfg.period) == 1 and cfg.periods == 4
    kind = cfg.period[0]
    assert kind == LayerKind(
        heads=8, kv_heads=8, d_ff=11008, rope=tm.Rope(theta=100000.0),
        eva=Eva(window=2048, chunk=16, init_std=0.01275), heads_first=0)
    shapes = jax.eval_shape(lambda k: transformer_init(k, cfg),
                            jax.random.PRNGKey(0))
    layer = shapes["period"]["0"]
    assert layer["wq"].shape == (4, 1, 4096, 1024)
    assert layer["wo"].shape == (4, 1, 1024, 4096)
    assert layer["phi"].shape == layer["mu"].shape == (4, 1, 8, 128)
    assert layer["phi"].dtype == jnp.float32
    assert shapes["head"].shape == (8 * 320, 4096)
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 620_015_616
    # the whole model's heads where none is asked for; its second quarter
    whole = config_from_published(PUBLISHED, max_seq=32768)
    assert whole.period[0].heads == 32 and whole.layers == 32
    second = config_from_published(PUBLISHED, layers=4, heads=8,
                                   heads_first=8)
    assert second.period[0].heads_first == 8
    # a configuration without the keys is what it was
    plain = {k: v for k, v in SMALL.items() if k not in (
        "attention_class", "num_pred_heads", "norm_add_unit_offset",
        "rms_norm_eps")}
    cfg = config_from_published(plain, layers=2)
    assert cfg.period[0].eva is None and cfg.pred_heads == 1
    assert not cfg.zero_centered_norm and cfg.norm_eps == 1e-6


def test_what_the_configuration_refuses():
    with pytest.raises(ValueError, match="no whole share"):
        config_from_published(dict(SMALL, num_key_value_heads=2), layers=2,
                              heads=2)
    with pytest.raises(ValueError, match="output matrix of their own"):
        TransformerConfig(pred_heads=2)
    eva_kind = LayerKind(heads=2, kv_heads=2, d_ff=8, eva=Eva(8, 2))
    with pytest.raises(ValueError, match="diffusion over blocks"):
        TransformerConfig(layers=1, period=(eva_kind,), diffusion_block=4)


def test_phi_and_mu_start_clipped_normal_times_init_std():
    cfg = small_config()
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    for name in ("phi", "mu"):
        leaf = np.asarray(params["period"]["0"][name])
        assert leaf.shape == (2, 1, 8, 16)
        assert np.abs(leaf).max() <= SMALL["init_std"] + 1e-9
        assert 0.3 * SMALL["init_std"] < leaf.std() < SMALL["init_std"]
    assert not np.array_equal(params["period"]["0"]["phi"],
                              params["period"]["0"]["mu"])
    # every norm starts at gain 1 + 0
    assert not np.asarray(params["ln_f"]).any()


@pytest.mark.parametrize("path, loss_chunk", [("off", 0), ("off", 16),
                                              ("on", 16)],
                         ids=["xla-dense", "xla-chunked", "kernels-chunked"])
def test_loss_and_gradients_match_the_plain_reference(monkeypatch, path,
                                                      loss_chunk):
    monkeypatch.setenv("HVDT_FLASH_ATTENTION", path)
    cfg = small_config(heads=4, heads_first=4, loss_chunk=loss_chunk)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    # phi and mu at a size at which the pooling is far from a mean
    params["period"]["0"].update(
        {n: 40.0 * params["period"]["0"][n] for n in ("phi", "mu")})
    tokens = tokens_of()
    got, g_got = jax.jit(jax.value_and_grad(
        lambda p: transformer_loss(p, tokens, cfg)))(params)
    want, g_want = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, tokens, config=SMALL)))(params)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    for (path_, a), b in zip(jax.tree_util.tree_leaves_with_path(g_got),
                             jax.tree.leaves(g_want)):
        np.testing.assert_allclose(a, b, atol=2e-6 + 2e-4 * np.abs(b).max(),
                                   err_msg=jax.tree_util.keystr(path_))
    assert np.abs(np.asarray(g_got["period"]["0"]["phi"])).max() > 1e-7


def test_the_shares_add_up():
    """The attention sublayer's output from heads 0-1, 2-3, 4-5, 6-7 as
    four held ranges (wq, wk, wv by columns, wo by rows, phi and mu by
    rows) sums to the uncut reference's; a share alone does not."""
    whole = small_config()
    params = transformer_init(jax.random.PRNGKey(3), whole)
    layer = jax.tree.map(lambda a: a[0, 0], params["period"]["0"])
    layer.update({n: 40.0 * layer[n] for n in ("phi", "mu")})
    x = jax.random.normal(jax.random.PRNGKey(4), (SEQ, SMALL["hidden_size"]))
    want = reference.attention(x, layer, SMALL)
    dh = whole.head_dim

    def share_of(first, held):
        cols = slice(first * dh, (first + held) * dh)
        p = dict(layer, wq=layer["wq"][:, cols], wk=layer["wk"][:, cols],
                 wv=layer["wv"][:, cols], wo=layer["wo"][cols],
                 phi=layer["phi"][first:first + held],
                 mu=layer["mu"][first:first + held])
        cfg = small_config(heads=held, heads_first=first)
        kind = cfg.period[0]
        assert (kind.heads, kind.kv_heads, kind.heads_first) == (
            held, held, first)
        positions = jnp.arange(SEQ)[None]
        return tm._attention(p, tm._norm(x[None], p["ln1"], cfg), positions,
                             cfg, kind)[0]

    shares = [share_of(first, 2) for first in (0, 2, 4, 6)]
    np.testing.assert_allclose(sum(shares), want, atol=3e-5)
    assert np.abs(np.asarray(shares[0] - want)).max() > 0.05
    # and the reference given a share computes that share
    cols = slice(2 * dh, 4 * dh)
    part = dict(layer, wq=layer["wq"][:, cols], wk=layer["wk"][:, cols],
                wv=layer["wv"][:, cols], wo=layer["wo"][cols],
                phi=layer["phi"][2:4], mu=layer["mu"][2:4])
    np.testing.assert_allclose(reference.attention(x, part, SMALL),
                               shares[1], atol=3e-5)


@pytest.mark.parametrize("loss_chunk", [0, 16, 7])
def test_the_n_target_loss_against_a_loop_over_the_heads(loss_chunk):
    cfg = small_config(loss_chunk=loss_chunk)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    tokens = tokens_of(samples=3)
    logits = transformer_apply(params, tokens, cfg)         # [b, l, n * v]
    n, v = cfg.pred_heads, cfg.vocab
    assert logits.shape == (3, SEQ, n * v) and logits.dtype == jnp.float32
    total, pairs = 0.0, 0
    for m in range(n):
        logp = jax.nn.log_softmax(logits[..., m * v:(m + 1) * v], -1)
        for i in range(SEQ - 1 - m):
            total -= logp[jnp.arange(3), i, tokens[:, i + 1 + m]].sum()
            pairs += 3
    assert pairs == 3 * sum(SEQ - 1 - m for m in range(n))
    np.testing.assert_allclose(transformer_loss(params, tokens, cfg),
                               total / pairs, rtol=2e-6)


@pytest.mark.parametrize("loss_chunk", [0, 16])
def test_one_prediction_head_is_transformer_loss_as_it_was_bit_for_bit(
        loss_chunk, monkeypatch):
    """``num_pred_heads`` 1 takes the path that was there: the same number
    as a configuration that never heard of the key, and the n-target
    function is not called."""
    one = dict(SMALL, num_pred_heads=1)
    without = {k: v for k, v in one.items() if k != "num_pred_heads"}
    fields = dict(layers=2, max_seq=SEQ, dtype=jnp.float32,
                  loss_chunk=loss_chunk)
    cfg_one = config_from_published(one, **fields)
    cfg_without = config_from_published(without, **fields)
    assert cfg_one == cfg_without and cfg_one.pred_heads == 1
    params = transformer_init(jax.random.PRNGKey(0), cfg_one)
    tokens = tokens_of()
    monkeypatch.setattr(tm, "_multi_target_xent", None)     # not reached
    a = jax.jit(lambda p: transformer_loss(p, tokens, cfg_one))(params)
    # as it was: the head's logits of all rows but the last against the
    # next token, chunked over the vocabulary or dense
    x = tm.transformer_hidden(params, tokens, cfg_one)
    if loss_chunk:
        b = jax.jit(lambda x: tm._chunked_xent(
            x[:, :-1], params["head"], tokens[:, 1:], loss_chunk))(x)
    else:
        logp = jax.nn.log_softmax(tm._head(params, x, cfg_one)[:, :-1], -1)
        b = -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).mean()
    np.testing.assert_allclose(a, b, rtol=1e-6)
    # and the n-target function at n = 1 is the same loss to rounding
    monkeypatch.undo()
    c = tm._multi_target_xent(x, params["head"], tokens, 1, loss_chunk)
    np.testing.assert_allclose(c, a, rtol=2e-6)


def test_the_operations_count_follows_the_heads_width_and_evas_pairs():
    cfg = config_from_published(PUBLISHED, layers=4, heads=8,
                                max_seq=32768)
    d, dh, h = 4096, 128, 8
    layer = (2 * d * 4 * h * dh                     # wq, wk, wv, wo
             + 2 * 2 * (65_028_096 / 32768) * h * dh + 2 * 3 * h * dh
             + 2 * d * 11008 * 3)
    assert transformer_flops_per_token(cfg) == pytest.approx(
        4 * layer + 2 * d * 320 * 8, rel=1e-12)
    # one target a row counts one vocabulary's columns, as before
    single = config_from_published(dict(PUBLISHED, num_pred_heads=1),
                                   layers=4, heads=8, max_seq=32768)
    assert transformer_flops_per_token(cfg) - \
        transformer_flops_per_token(single) == 2 * d * 320 * 7
    uniform = TransformerConfig(vocab=100, layers=2, d_model=64, heads=4,
                                kv_heads=4, d_ff=128, max_seq=32)
    assert transformer_flops_per_token(uniform) == 2 * (
        2 * 64 * 4 * 64 + 2 * 2 * 32 * 64 + 2 * 64 * 128 * 3) + 2 * 64 * 100


@pytest.mark.parametrize("path", ["off", "on"], ids=["xla", "kernels"])
def test_the_step_carries_the_scopes_the_readers_take(monkeypatch, path):
    """``hvdt.eva`` inside ``hvdt.attention.core``, its children
    ``hvdt.eva.summary`` and ``hvdt.eva.core``, in the forward, the
    recompute and the backward; the head under ``hvdt.loss``."""
    monkeypatch.setenv("HVDT_FLASH_ATTENTION", path)
    cfg = small_config(heads=4)
    params = jax.eval_shape(lambda k: transformer_init(k, cfg),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, SEQ), jnp.int32)
    text = jax.jit(jax.value_and_grad(
        lambda p, t: transformer_loss(p, t, cfg))).lower(
            params, tokens).compile().as_text()
    inside = "hvdt.attention/hvdt.attention.core/hvdt.eva/"
    for child in ("hvdt.eva.summary", "hvdt.eva.core"):
        for wrapper in ("jvp()/", "rematted_computation/", "transpose(jvp())/"):
            assert any(wrapper in line and inside + child in line
                       for line in text.splitlines()), (child, wrapper)
    assert "hvdt.eva.core/hvdt.eva.summary" not in text
    assert "jvp(hvdt.loss)" in text and "transpose(jvp(hvdt.loss))" in text
