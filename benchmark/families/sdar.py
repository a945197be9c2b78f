"""The ``sdar`` family: a published decoder LM (grouped-query attention
with q / k norm, a softmax-routed sparse feed-forward in every layer, an
untied head) trained by DIFFUSION OVER BLOCKS, on one chip's share of its
deployment, through the repo's pattern model
(``horovod_tpu.models.config_from_published`` with ``diffusion_block``)
under ``transformer_block_diffusion_loss``: the model runs on the 2 L rows
[noisy ; clean] of L tokens under the block mask.  The configuration file
keeps the source's own keys for every width; ``layers``, ``experts`` (held,
from ``experts_first``) and ``vocab`` (rows held) are the share.

A batch is ``(tokens, t, masked)``, all from the slot's key and so from
``--seed``: the tokens, and the noise (a level t a block, stratified over
a sequence's blocks, and the masked positions) by the library's
``block_diffusion_corrupt``.  Every slot of the pool and the check's
sample carries its own realisation.

The weights are ``transformer_init``'s from the seed, but for one thing: the
router starts balanced over the chips that share a layer
(``rank_tied_router``), as the deployment's trained router is and a random
one is not, so that every seed gives this chip its share of the picks.

Also here, because the per-layer readers of its cell use them: what one
call of each block-mask kernel needs, from shapes (``visible_pairs_bd``,
``flash_bd_call_cost``).
"""

from __future__ import annotations

import functools

from benchmark.families import Family, dtype_of
from benchmark.families.laguna import optimizer_of
from benchmark.reference import sdar as reference


def visible_pairs_bd(seq: int, block: int) -> int:
    """Query-key pairs a head computes over the 2 x ``seq`` rows of one
    sequence under the block mask: clean on clean seq (seq + block) / 2,
    noisy on clean seq (seq - block) / 2, noisy on noisy seq x block."""
    return seq * seq + seq * block


def flash_bd_call_cost(*, batch: int, seq: int, heads: int, kv_heads: int,
                       head_dim: int, block: int, backward: bool = False):
    """(operations, bytes) of one call of a block-mask flash kernel on the
    2 x ``seq`` bf16 rows of each sequence with grouped queries, over the
    visible pairs only, as ``families.laguna.flash_call_cost`` counts a
    causal call: two products forward, five backward; q, o (dO, dq) at
    ``heads`` heads, k, v at ``kv_heads``, dk and dv written per query
    head as the kernel writes them."""
    pairs = batch * heads * visible_pairs_bd(seq, block)
    tensor = batch * 2 * seq * head_dim * 2         # bytes a head
    if backward:
        return (5 * 2.0 * pairs * head_dim,
                float(tensor * (5 * heads + 2 * kv_heads)))
    return (2 * 2.0 * pairs * head_dim,
            float(tensor * (2 * heads + 2 * kv_heads)))


def rank_tied_router(w_router, held: int):
    """A router ``[..., d, experts]`` that starts balanced over the ranks
    that share its layer, ``held`` experts each: the first rank's drawn
    columns repeated on every rank.  A row scores the copies of a column
    alike, so its picks are the copies of its best columns, as many on
    one rank as on another, WHATEVER the row is: each rank gets picks /
    ranks of every row's picks, where at a random start a layer's rows,
    nearly one vector after an attention that averages thousands of keys,
    pick nearly the same experts and a rank holds none or several of them
    by the seed's accident.  Training unties the copies: each rank's
    experts send their own gradient."""
    import jax.numpy as jnp

    ranks = w_router.shape[-1] // held
    return jnp.tile(w_router[..., :held],
                    (1,) * (w_router.ndim - 1) + (ranks,))


def layer_macs(config: dict, seq: int) -> dict:
    """Forward multiply-adds a DATA token of one layer, by part: a token
    is two rows of every product (the noisy and the clean stream)."""
    d, dh = config["hidden_size"], config["head_dim"]
    h, hk = config["num_attention_heads"], config["num_key_value_heads"]
    # The router over every routed expert; of a row's picks, experts /
    # num_experts land on held experts in expectation (one of eight at 16
    # of 128).
    held = (config["num_experts_per_tok"] * config["experts"]
            / config["num_experts"])
    return {
        # wq, wk, wv, wo, on both rows
        "projections": 2 * (d * h * dh * 2 + d * hk * dh * 2),
        # q.k and p.v over the pairs the mask shows, a token's share
        "scores": 2 * h * dh * visible_pairs_bd(
            seq, config["block_length"]) / seq,
        "feed_forward": 2 * (d * config["num_experts"] + held * 3 * d
                             * config["moe_intermediate_size"]),
    }


def flops_per_token(config: dict, seq: int) -> float:
    """Forward + backward matmul operations per DATA token, from shapes: 2
    per multiply-add, backward twice the forward, recompute not counted;
    the layers held here on both streams and the head over the held rows
    of the vocabulary on the noisy stream alone."""
    macs = config["layers"] * sum(layer_macs(config, seq).values())
    return 3.0 * 2.0 * (macs + config["hidden_size"] * config["vocab"])


def build(config: dict, traffic: dict) -> Family:
    import jax

    from horovod_tpu.models import (block_diffusion_corrupt,
                                    config_from_published, transformer_init,
                                    transformer_block_diffusion_loss)

    seq = traffic["seq"]
    if traffic["block_length"] != config["block_length"]:
        raise ValueError(
            f"the cell's traffic cuts blocks of {traffic['block_length']} "
            f"and its configuration of {config['block_length']}")
    cfg = config_from_published(
        config, layers=config["layers"], experts=config["experts"],
        experts_first=config["experts_first"], vocab=config["vocab"],
        router_score=config["router_score"], qk_norm=config["qk_norm"],
        diffusion_block=config["block_length"], max_seq=seq,
        dtype=dtype_of(config["compute_dtype"]),
        param_dtype=dtype_of(config["param_dtype"]),
        remat=config["remat"] != "none",
        remat_policy=config["remat"] if config["remat"] != "none" else "full",
        loss_chunk=config["loss_chunk"])
    skew = traffic.get("token_skew", 1)
    mask_id = cfg.vocab - 1
    ranks, ragged = divmod(config["num_experts"], config["experts"])
    if ragged or config["num_experts_per_tok"] % ranks:
        raise ValueError(
            f"{config['num_experts_per_tok']} picks over "
            f"{config['num_experts']} experts, {config['experts']} a rank, "
            "cannot start as many on one rank as on another")

    def init(key):
        params = transformer_init(key, cfg)
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: rank_tied_router(leaf, config["experts"])
            if path[-1].key == "w_router" else leaf, params)

    def make_batch(key, samples):
        # floor((held rows - 1) * u^skew): ids drawn from the held slice of
        # the vocabulary short of its last row, the mask token, skewed so
        # that there is something to learn.
        k_tokens, k_noise = jax.random.split(key)
        u = jax.random.uniform(k_tokens, (samples, seq))
        tokens = (mask_id * u ** skew).astype("int32")
        _, t, masked = block_diffusion_corrupt(
            k_noise, tokens, block=cfg.diffusion_block, mask_id=mask_id)
        return tokens, t, masked

    return Family(
        init=init,
        loss_fn=lambda p, tokens, t, masked:
            transformer_block_diffusion_loss(p, tokens, t, masked, cfg),
        optimizer=optimizer_of(config["optimizer"]),
        make_batch=make_batch,
        unit="tokens",
        units_per_sample=seq,
        flops_per_unit=flops_per_token(config, seq),
        sample_size=traffic.get("sample_sequences", 1),
        reference_loss=functools.partial(reference.loss, config=config),
        # One sample sequence is too few for `auto` to choose the kernels
        # by itself at every length: pin the path the step was seen to take.
        sample_env=lambda mosaic: {
            "HVDT_FLASH_ATTENTION": "on" if mosaic else "off"},
        tolerances=config["tolerances"])
