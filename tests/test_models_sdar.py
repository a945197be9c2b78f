"""SDAR's model through ``TransformerConfig`` (``diffusion_block``) and
``transformer_block_diffusion_loss`` against the plain reference
``benchmark/reference/sdar.py`` in float32: the same loss and gradients to
rounding on the XLA attention path and, through the interpreter, on the
block-mask flash kernels; THE TWO-STREAM TRICK AGAINST THE DEFINITION (for
every block b, a pass over [x_0 of the blocks before b ; x_t of block b]
alone gives the noisy half's rows of block b); the corruption function's
marginals; what ``config_from_published`` makes of the source's keys; and
what the objective refuses."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.reference import sdar as reference  # noqa: E402
from horovod_tpu.models import (LayerKind, TransformerConfig,  # noqa: E402
                                block_diffusion_corrupt,
                                config_from_published,
                                transformer_block_diffusion_loss,
                                transformer_flops_per_token,
                                transformer_init, transformer_loss)

with open(os.path.join(REPO, "benchmark", "configs",
                       "sdar_30b_a3b.json")) as f:
    PUBLISHED = json.load(f)

# The published layer at a size the CPU takes: two layers, 8 query heads
# over 2 of 16, 16 experts of which 4 are held (experts 4..7), 4 picks.
SMALL = dict(
    PUBLISHED, hidden_size=64, head_dim=16, num_attention_heads=8,
    num_key_value_heads=2, num_attention_heads_per_layer=[8] * 48,
    moe_intermediate_size=32, num_experts=16, num_experts_per_tok=4,
    vocab_size=256, layers=2, experts=4, experts_first=4, vocab=97)
SEQ = 128


def small_config(block=4, **fields):
    c = dict(SMALL, block_length=block)
    fields = dict(dict(max_seq=SEQ, dtype=jnp.float32, remat=True,
                       loss_chunk=40), **fields)
    return c, config_from_published(
        c, layers=c["layers"], experts=c["experts"],
        experts_first=c["experts_first"], vocab=c["vocab"],
        router_score=c["router_score"], qk_norm=c["qk_norm"],
        diffusion_block=block, **fields)


def batch_of(cfg, samples=2, seed=1):
    k_tokens, k_noise = jax.random.split(jax.random.PRNGKey(seed))
    tokens = jax.random.randint(k_tokens, (samples, SEQ), 0, cfg.vocab - 1)
    _, t, masked = block_diffusion_corrupt(
        k_noise, tokens, block=cfg.diffusion_block, mask_id=cfg.vocab - 1)
    return tokens, t, masked


@pytest.mark.parametrize("block", [4, 32])
@pytest.mark.parametrize("path, loss_chunk", [("off", 0), ("off", 40),
                                              ("on", 40)],
                         ids=["xla-dense", "xla-chunked", "kernels-chunked"])
def test_loss_and_gradients_match_the_plain_reference(monkeypatch, path,
                                                      loss_chunk, block):
    monkeypatch.setenv("HVDT_FLASH_ATTENTION", path)
    published, cfg = small_config(block, loss_chunk=loss_chunk)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    batch = batch_of(cfg)
    got, g_got = jax.jit(jax.value_and_grad(
        lambda p: transformer_block_diffusion_loss(p, *batch, cfg)))(params)
    want, g_want = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, *batch, config=published)))(params)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    flat = jax.tree_util.tree_leaves_with_path(g_got)
    for (path_, a), b in zip(flat, jax.tree.leaves(g_want)):
        np.testing.assert_allclose(
            a, b, rtol=2e-4, atol=2e-6 * float(jnp.abs(b).max()) + 1e-9,
            err_msg=jax.tree_util.keystr(path_))


@pytest.mark.parametrize("block", [4, 32])
def test_the_two_stream_pass_is_the_per_block_definition(block):
    """The definition of the objective, block by block: to score block b
    the model reads the CLEAN tokens of the blocks before it and the NOISY
    tokens of block b, positions 0 .. (b + 1) B - 1, the clean prefix
    block-causal and the noisy block seeing all of the prefix and itself.
    The one pass over [x_t ; x_0] gives those rows for every b at once."""
    published, cfg = small_config(block)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), transformer_init(
        jax.random.PRNGKey(0), cfg))
    tokens, _, masked = batch_of(cfg, samples=1, seed=5)
    ids, masked = tokens[0], masked[0]
    with jax.default_matmul_precision("highest"):
        both = jax.jit(lambda p: reference.noisy_nll(
            p, ids, masked, published))(params)
        x_t = jnp.where(masked, cfg.vocab - 1, ids)

        @jax.jit
        def alone(p, b):
            """Block b by itself, on SEQ rows so that one program serves
            every b: rows past the block are padding no real row sees."""
            row = jnp.arange(SEQ)
            blk = row // block
            rows = jnp.where(blk < b, ids, x_t)
            seen = (blk[None, :] <= blk[:, None]) & (blk[None, :] <= b) \
                | (row[None, :] == row[:, None])
            x = reference.hidden(p, rows, row, seen, published)
            return reference.token_nll(p, x, ids)

        for b in range(SEQ // block):
            mine = slice(b * block, (b + 1) * block)
            np.testing.assert_allclose(
                alone(params, b)[mine], both[mine], rtol=2e-5, atol=2e-5,
                err_msg=f"block {b}")
    # and the rows do depend on the noise: a clean block reads otherwise
    assert float(jnp.abs(both - jax.jit(lambda p: reference.noisy_nll(
        p, ids, jnp.zeros_like(masked), published))(params)).max()) > 1e-2


def test_the_loss_is_the_weighted_masked_sum_of_the_noisy_rows():
    """(1 / (batch L)) sum_b (1 / t_b) sum_{i in b, masked} nll_i, from the
    reference's per-row terms; unmasked rows and the clean half add
    nothing."""
    published, cfg = small_config(4)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    tokens, t, masked = batch_of(cfg)
    got = jax.jit(lambda p: transformer_block_diffusion_loss(
        p, tokens, t, masked, cfg))(params)
    with jax.default_matmul_precision("highest"):
        rows = jnp.stack([reference.noisy_nll(params, tokens[i], masked[i],
                                              published) for i in range(2)])
    want = (rows * masked / jnp.repeat(t, 4, 1)).sum() / tokens.size
    np.testing.assert_allclose(got, want, rtol=2e-6)
    # nothing masked: no term
    assert float(transformer_block_diffusion_loss(
        params, tokens, t, jnp.zeros_like(masked), cfg)) == 0.0


@pytest.mark.parametrize("block", [4, 32])
def test_the_corruption_functions_marginals(block):
    """t in [eps, 1), stratified over a sequence's blocks with a uniform
    marginal; a block's masked share within binomial error of its t; the
    mask id in x_t exactly where masked, and never in the data."""
    batch, length, eps, mask_id = 64, 2048, 1e-3, 999
    n = length // block
    tokens = jax.random.randint(jax.random.PRNGKey(0), (batch, length), 0,
                                mask_id)
    x_t, t, masked = jax.jit(lambda k: block_diffusion_corrupt(
        k, tokens, block=block, mask_id=mask_id, eps=eps))(
            jax.random.PRNGKey(7))
    t, masked, x_t = np.asarray(t), np.asarray(masked), np.asarray(x_t)
    assert t.shape == (batch, n) and masked.shape == (batch, length)
    assert t.min() >= eps and t.max() < 1.0
    # stratified: a sequence's levels fill the n strata of [eps, 1) once
    strata = np.sort(np.floor((t - eps) / (1 - eps) * n).astype(int), 1)
    np.testing.assert_array_equal(strata, np.tile(np.arange(n), (batch, 1)))
    # each block's own t is uniform: its mean over sequences near 1 / 2
    assert abs(t[:, 0].mean() - 0.5) < 4 * np.sqrt(1 / 12 / batch)
    # the masked share of a block against its t, pooled by level
    share = masked.reshape(batch, n, block).mean(-1)
    z = (share - t) / np.sqrt(np.maximum(t * (1 - t), 1e-9) / block)
    assert abs(z.mean()) < 4 / np.sqrt(z.size) and 0.9 < z.std() < 1.1
    assert abs(masked.mean() - t.mean()) < 4 * np.sqrt(
        0.25 / masked.size)
    np.testing.assert_array_equal(x_t == mask_id, masked)
    np.testing.assert_array_equal(x_t[~masked], np.asarray(tokens)[~masked])
    with pytest.raises(ValueError, match="whole blocks"):
        block_diffusion_corrupt(jax.random.PRNGKey(0), tokens[:, :30],
                                block=4, mask_id=mask_id)


def test_config_from_published_on_the_sources_keys():
    """Every key of the source's config.json, and nothing derived: period
    1, 32 / 4 heads of 128, every layer sparse with 128 experts top 8
    normalised, no shared expert, no window, RoPE 1e6 on the whole head,
    an untied head."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as f:
            source = [json.loads(line) for line in f if line.strip()]
        source = [r for r in source
                  if r["name"] == "SDAR-30B-A3B-Chat"][0]["config"]
    else:
        source = {k: v for k, v in PUBLISHED.items() if k not in (
            "layer_types", "mlp_layer_types",
            "num_attention_heads_per_layer")}
    for published in (source, PUBLISHED):
        cfg = config_from_published(
            published, layers=6, experts=16, vocab=18992,
            router_score="softmax", qk_norm=True, diffusion_block=4)
        kind = LayerKind(heads=32, kv_heads=4, sparse=True,
                         rope=cfg.period[0].rope)
        assert cfg.leading == () and cfg.period == (kind,)
        assert cfg.periods == 6 and cfg.layers == 6
        assert (cfg.d_model, cfg.head_dim, cfg.vocab) == (2048, 128, 18992)
        assert kind.rope.theta == 1e6 and kind.rope.dim == 0
        assert kind.window is None and kind.linear is None
        moe = cfg.moe
        assert (moe.held, moe.routed, moe.per_token, moe.d_ff, moe.first,
                moe.score, moe.normalize, moe.scale, moe.shared_d_ff) == (
                    16, 128, 8, 768, 0, "softmax", True, 1.0, 0)
        assert not cfg.tie_head and cfg.qk_norm and cfg.out_gate == ""
        assert cfg.diffusion_block == 4
    shapes = jax.eval_shape(lambda k: transformer_init(k, cfg),
                            jax.random.PRNGKey(0))
    layer = shapes["period"]["0"]
    assert layer["wq"].shape == (6, 1, 2048, 4096)
    assert layer["wk"].shape == (6, 1, 2048, 512)
    assert layer["q_norm"].shape == layer["k_norm"].shape == (6, 1, 128)
    assert layer["w_router"].shape == (6, 1, 2048, 128)
    assert layer["w_up"].shape == (6, 1, 16, 2048, 768)
    assert "ws_up" not in layer and "wg" not in layer
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 645_623_296


def test_positions_repeat_over_the_two_streams(monkeypatch):
    """RoPE sees a token's place in its sequence: the clean half of
    ``transformer_hidden``'s rows carries the same positions as the noisy
    half, so a clean row's output does not depend on which half it is fed
    in but for the mask."""
    from horovod_tpu.models import transformer as tfm

    seen = []
    real = tfm._rope_tables
    monkeypatch.setattr(tfm, "_rope_tables", lambda positions, *a: (
        seen.append(positions), real(positions, *a))[1])
    _, cfg = small_config(4, remat=False)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    tfm.transformer_hidden(params, jnp.zeros((1, 2 * SEQ), jnp.int32), cfg)
    np.testing.assert_array_equal(
        np.asarray(seen[0])[0], np.tile(np.arange(SEQ), 2))


def test_flops_count_both_streams_and_one_head():
    _, cfg = small_config(4)
    plain = config_from_published(
        dict(SMALL), layers=2, experts=4, experts_first=4, vocab=97,
        router_score="softmax", qk_norm=True, max_seq=SEQ)
    head = 2 * cfg.d_model * cfg.vocab
    assert transformer_flops_per_token(cfg) - head == pytest.approx(
        2 * (transformer_flops_per_token(plain) - head))


def test_what_the_objective_refuses():
    window = LayerKind(heads=2, kv_heads=2, d_ff=32, window=8)
    with pytest.raises(ValueError, match="full softmax attention"):
        TransformerConfig(layers=2, d_model=32, heads=2, kv_heads=2,
                          period=(window,), diffusion_block=4)
    with pytest.raises(ValueError, match="full softmax attention"):
        TransformerConfig(layers=2, d_model=32, heads=2, sp=2,
                          diffusion_block=4)
    # the next-token objective is untouched by the field's default
    cfg = TransformerConfig(vocab=64, layers=1, d_model=32, heads=2,
                            kv_heads=2, d_ff=64, dtype=jnp.float32)
    assert cfg.diffusion_block == 0
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((1, 16), jnp.int32)
    assert np.isfinite(float(transformer_loss(params, tokens, cfg)))
