"""The ``lfm2`` family: a published hybrid decoder LM (LFM2-MoE:
double-gated short-convolution layers with a grouped-query attention layer
with q / k norm among every few, leading dense SwiGLU layers and then a
sparse feed-forward whose router picks by sigmoid score plus a selection
bias and weighs by the score alone, a tied head) on one chip's share of
its deployment, through the repo's pattern model
(``horovod_tpu.models.config_from_published``) under next-token cross
entropy.  The configuration file keeps the source's own keys for every
width; ``layers`` (held, from ``layers_first``), ``experts`` (held, from
``experts_first``) and ``vocab`` (rows held) are the share.

The weights are ``transformer_init``'s from the seed, but for the route's
start: the router's columns and a drawn selection bias are tied over the
ranks that share a layer (``families.sdar.rank_tied_router``), so that every
row's picks are the copies of its best score-plus-bias column, one a rank:
every seed lands one pick of every token here, as a trained, balanced
router does in expectation and a random one does not.

Also here, because the per-layer readers of its cell use them: what a
short-convolution layer needs a step, from shapes (``sconv_cost``; its
gates and taps alone, ``sconv_conv_cost``), and which layers are sparse
(``sparse_layers``).
"""

from __future__ import annotations

import functools

from benchmark.families import Family, dtype_of
from benchmark.families.laguna import optimizer_of, visible_pairs
from benchmark.families.sdar import rank_tied_router
from benchmark.reference import lfm2 as reference


def held_layers(config: dict) -> range:
    """The indices, in the published stack, of the layers held here."""
    first = config.get("layers_first", 0)
    return range(first, first + config["layers"])


def sparse_layers(config: dict) -> int:
    """How many of the held layers have the expert layer."""
    return sum(i >= config["num_dense_layers"] for i in held_layers(config))


def conv_layers(config: dict) -> int:
    """How many of the held layers mix by the short convolution."""
    return sum(config["layer_types"][i] == "conv"
               for i in held_layers(config))


def sconv_conv_cost(config: dict, *, tokens: int):
    """(operations, bytes) a step of ONE short-convolution layer's gates
    and taps on ``tokens`` tokens, from shapes alone, whatever implements
    them.  Bytes: a pass reads B, C and X (bf16, ``hidden_size`` channels
    each) and writes the gated sum (bf16), four passes' worth (forward,
    recompute, and the backward reads them and the cotangent and writes
    three cotangents: twice a pass).  Operations (elementwise, none on the
    MXU): a channel's ``conv_L_cache`` products B X and multiply-adds and
    the output gate, the same four passes; the bound is the bytes'."""
    d, taps = config["hidden_size"], config["conv_L_cache"]
    a_pass = tokens * 4 * d * 2
    return 4.0 * tokens * d * (3 * taps + 1), float(4 * a_pass)


def sconv_cost(config: dict, *, tokens: int):
    """(operations, bytes) a step of ONE short-convolution layer's whole
    mixer on ``tokens`` tokens, from shapes alone.  Operations: the input
    projection's 3 d^2 and the output projection's d^2 multiply-adds a
    token forward, the same again in the recompute, twice that in the
    backward (each product's two operand gradients), 2 a multiply-add.
    Bytes: the least a pass moves, the layer's input read and its output
    written (bf16) and the two matrices read (bf16), four passes' worth;
    the gates and taps add none where they ride in the products' fusions
    (``sconv_conv_cost`` is what they move as a pass of their own)."""
    d = config["hidden_size"]
    a_pass = tokens * 2 * d * 2 + 4 * d * d * 2
    return 2.0 * 4 * 4 * d * d * tokens, float(4 * a_pass)


def layer_macs(config: dict, index: int, seq: int) -> dict:
    """Forward multiply-adds a token of layer ``index``, by part."""
    d = config["hidden_size"]
    if config["layer_types"][index] == "conv":
        out = {
            # w_in ([B | C | X]) and w_out
            "projections": 4 * d * d,
            "taps": config["conv_L_cache"] * d}
    else:
        h, hk = config["num_attention_heads"], config["num_key_value_heads"]
        dh = d // h
        out = {
            # wq, wk, wv, wo
            "projections": 2 * d * h * dh + 2 * d * hk * dh,
            # q.k and p.v over the causal half, averaged over the rows
            "scores": 2 * h * dh * visible_pairs(seq) / seq}
    if index < config["num_dense_layers"]:
        out["feed_forward"] = 3 * d * config["intermediate_size"]
    else:
        # The router over every routed expert; of a row's picks, experts /
        # num_experts land on held experts (one of four at 16 of 64).
        held = (config["num_experts_per_tok"] * config["experts"]
                / config["num_experts"])
        out["feed_forward"] = (d * config["num_experts"] + held * 3 * d
                               * config["moe_intermediate_size"])
    return out


def flops_per_token(config: dict, seq: int) -> float:
    """Forward + backward matmul operations per token, from shapes: 2 per
    multiply-add, backward twice the forward, recompute not counted; the
    layers held here and the tied head over the held rows of the
    vocabulary, once (the embedding gather is no matmul)."""
    macs = sum(sum(layer_macs(config, i, seq).values())
               for i in held_layers(config))
    return 3.0 * 2.0 * (macs + config["hidden_size"] * config["vocab"])


def build(config: dict, traffic: dict) -> Family:
    import jax

    from horovod_tpu.models import (config_from_published, transformer_init,
                                    transformer_loss)

    seq = traffic["seq"]
    cfg = config_from_published(
        config, layers=config["layers"], layers_first=config["layers_first"],
        experts=config["experts"], experts_first=config["experts_first"],
        vocab=config["vocab"], router_score=config["router_score"],
        qk_norm=config["qk_norm"],
        normalize_eps=config["router_normalize_eps"], max_seq=seq,
        dtype=dtype_of(config["compute_dtype"]),
        param_dtype=dtype_of(config["param_dtype"]),
        remat=config["remat"] != "none",
        remat_policy=config["remat"] if config["remat"] != "none" else "full",
        loss_chunk=config["loss_chunk"])
    skew = traffic.get("token_skew", 1)
    ranks, ragged = divmod(config["num_experts"], config["experts"])
    if ragged or config["num_experts_per_tok"] % ranks:
        raise ValueError(
            f"{config['num_experts_per_tok']} picks over "
            f"{config['num_experts']} experts, {config['experts']} a rank, "
            "cannot start as many on one rank as on another")
    bias_std = config["router_bias_std"]

    def init(key):
        params = transformer_init(key, cfg)
        # a stream of its own beside transformer_init's splits of the key
        bias_keys = iter(jax.random.split(jax.random.fold_in(key, 64), 64))

        def start(path, leaf):
            # The route's start (the module's docstring): the first rank's
            # columns, and a bias drawn for them, on every rank.
            if path[-1].key == "w_router":
                return rank_tied_router(leaf, config["experts"])
            if path[-1].key == "router_bias":
                return rank_tied_router(bias_std * jax.random.normal(
                    next(bias_keys), leaf.shape, leaf.dtype),
                    config["experts"])
            return leaf

        return jax.tree_util.tree_map_with_path(start, params)

    def make_batch(key, samples):
        # floor(held rows * u^skew): ids drawn from the held slice of the
        # vocabulary, skewed so that there is something to learn.
        u = jax.random.uniform(key, (samples, seq))
        return ((cfg.vocab * u ** skew).astype("int32"),)

    return Family(
        init=init,
        loss_fn=lambda p, tokens: transformer_loss(p, tokens, cfg),
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
