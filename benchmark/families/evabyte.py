"""The ``evabyte`` family: a published byte-level decoder LM whose attention
is EVA (exact softmax inside aligned windows joined in one softmax with a
learned summary of every chunk of every earlier window) and whose head
predicts several bytes a row, on one chip's share of its deployment,
through the repo's pattern model
(``horovod_tpu.models.config_from_published`` with ``heads`` /
``heads_first``) under ``transformer_loss``.  The configuration file keeps
the source's own keys for every width; ``layers`` and ``heads`` (held,
from ``heads_first``) are the share: attention divided by heads, the
feed-forward and the vocabulary whole.

Also here, because the per-layer readers of its cell use them: the pairs
EVA's two masks show (``eva_visible_pairs``) and what one pass of the
aggregation needs, from shapes (``eva_core_cost``).
"""

from __future__ import annotations

import functools

from benchmark.families import Family, dtype_of, make_optimizer
from benchmark.reference import evabyte as reference


def eva_visible_pairs(seq: int, window: int, chunk: int):
    """(exact, summary) query-key pairs a head computes over one sequence
    of whole windows: row i sees the i % window + 1 keys of its own window
    up to itself, and the window / chunk summaries of each of the i //
    window windows before its own."""
    windows = seq // window
    return (windows * window * (window + 1) // 2,
            window * (window // chunk) * (windows * (windows - 1) // 2))


def eva_core_cost(*, batch: int, seq: int, heads: int, head_dim: int,
                  window: int, chunk: int, backward: bool = False):
    """(operations, bytes) of one pass of EVA's aggregation on bf16
    operands, over the visible pairs only, whatever implements it.
    Forward: the score and the value product; q read and o written, k, v
    and the summaries read once.  Backward: five products (the scores
    again, dP, dv, dq, dk); q, dO, k, v and the summaries read, dq, dk, dv
    and the summaries' cotangents written once.  The f32 row statistics
    are 2 / head_dim of a tensor and are left out."""
    pairs = batch * heads * sum(eva_visible_pairs(seq, window, chunk))
    tensor = batch * heads * seq * head_dim * 2         # bytes, all heads
    summaries = 2 * tensor / chunk                      # k~ and v~
    if backward:
        return (5 * 2.0 * pairs * head_dim,
                float(7 * tensor + 2 * summaries))
    return 2 * 2.0 * pairs * head_dim, float(4 * tensor + summaries)


def layer_macs(config: dict, seq: int) -> dict:
    """Forward multiply-adds a byte of one layer, by part."""
    d = config["hidden_size"]
    dh = d // config["num_attention_heads"]
    h = config["heads"]
    window, chunk = config["window_size"], config["chunk_size"]
    return {
        # wq, wk, wv, wo at the heads held
        "projections": 4 * d * h * dh,
        # k . phi and the two pooled sums, a key
        "pooling": 3 * h * dh,
        # q.k and p.v over the pairs the two masks show, a row's share
        "scores": 2 * h * dh * sum(eva_visible_pairs(seq, window, chunk))
        / seq,
        "feed_forward": 3 * d * config["intermediate_size"],
    }


def flops_per_token(config: dict, seq: int) -> float:
    """Forward + backward matmul operations per byte of data, from shapes:
    2 per multiply-add, backward twice the forward, recompute not counted;
    the layers held here and the head at its own width, num_pred_heads x
    vocab_size columns, once (the embedding gather is no matmul)."""
    macs = config["layers"] * sum(layer_macs(config, seq).values())
    return 3.0 * 2.0 * (macs + config["hidden_size"] * config["vocab_size"]
                        * config["num_pred_heads"])


def build(config: dict, traffic: dict) -> Family:
    import jax

    from horovod_tpu.models import (config_from_published, transformer_init,
                                    transformer_loss)

    seq = traffic["seq"]
    for key, published in (("pred_heads", "num_pred_heads"),
                           ("window", "window_size"),
                           ("chunk", "chunk_size")):
        if traffic[key] != config[published]:
            raise ValueError(
                f"the cell's traffic states {key} {traffic[key]} and its "
                f"configuration {published} {config[published]}")
    cfg = config_from_published(
        config, layers=config["layers"], heads=config["heads"],
        heads_first=config["heads_first"], max_seq=seq,
        dtype=dtype_of(config["compute_dtype"]),
        param_dtype=dtype_of(config["param_dtype"]),
        remat=config["remat"] != "none",
        remat_policy=config["remat"] if config["remat"] != "none" else "full",
        loss_chunk=config["loss_chunk"])
    skew = traffic.get("token_skew", 1)

    def make_batch(key, samples):
        # floor(320 * u^skew): byte ids skewed as the LM cells skew theirs,
        # so that there is something to learn in a batch seen once.
        u = jax.random.uniform(key, (samples, seq))
        return ((cfg.vocab * u ** skew).astype("int32"),)

    return Family(
        init=lambda key: transformer_init(key, cfg),
        loss_fn=lambda p, tokens: transformer_loss(p, tokens, cfg),
        optimizer=make_optimizer(config["optimizer"]),
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
