"""The ``laguna`` family: a published layer-pattern decoder LM (full and
sliding-window attention with their own head counts, a per-head output
gate, a leading dense feed-forward, sparse ones with a shared expert, an
untied head) on one chip's share of its deployment, through the repo's
pattern model (``horovod_tpu.models.config_from_published``) under
next-token cross entropy.  The configuration file keeps the source's own
keys for every width; ``layers``, ``experts`` (held, from
``experts_first``) and ``vocab`` (rows held) are the share.

Also here, because the per-layer readers of its cells use them: what one
call of each kernel needs, from shapes (``flash_call_cost``,
``expert_products_cost``).
"""

from __future__ import annotations

import functools

from benchmark.families import Family, dtype_of, make_optimizer
from benchmark.reference import laguna as reference


def visible_pairs(seq: int, window=None) -> int:
    """Query-key pairs a causal head computes over one sequence: row i
    sees min(i + 1, window) keys."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layer_macs(config: dict, index: int, seq: int) -> dict:
    """Forward multiply-adds a token of layer ``index``, by part."""
    d, dh = config["hidden_size"], config["head_dim"]
    h = config["num_attention_heads_per_layer"][index]
    hk = config["num_key_value_heads"]
    sliding = config["layer_types"][index] == "sliding_attention"
    out = {
        # wq, wk, wv, wo and the per-head gate
        "projections": d * h * dh * 2 + d * hk * dh * 2 + d * h,
        # q.k and p.v over the pairs a row sees, averaged over the rows
        "scores": 2 * h * dh * visible_pairs(
            seq, config["sliding_window"] if sliding else None) / seq,
    }
    if config["mlp_layer_types"][index] == "dense":
        out["feed_forward"] = 3 * d * config["intermediate_size"]
    else:
        # The router over every routed expert; of a token's picks,
        # experts / num_experts land on held experts in expectation (one
        # of eight at 32 of 256); the shared expert.
        held = (config["num_experts_per_tok"] * config["experts"]
                / config["num_experts"])
        out["feed_forward"] = (
            d * config["num_experts"]
            + held * 3 * d * config["moe_intermediate_size"]
            + 3 * d * config["shared_expert_intermediate_size"])
    return out


def flops_per_token(config: dict, seq: int) -> float:
    """Forward + backward matmul operations per token, from shapes: 2 per
    multiply-add, backward twice the forward, recompute not counted; the
    layers held here and the head over the held rows of the vocabulary
    (the embedding gather is no matmul)."""
    macs = sum(sum(layer_macs(config, i, seq).values())
               for i in range(config["layers"]))
    return 3.0 * 2.0 * (macs + config["hidden_size"] * config["vocab"])


def flash_call_cost(*, batch: int, seq: int, heads: int, kv_heads: int,
                    head_dim: int, window=None, backward: bool = False):
    """(operations, bytes) of one call of a local flash kernel on bf16
    operands with grouped queries, over the visible pairs only.  Forward:
    the score and the value product; q and o at ``heads`` heads, k and v at
    ``kv_heads``.  Backward: five products (the scores again, dP, dv, dq,
    dk); q, dO read and dq written at ``heads`` heads, k, v read at
    ``kv_heads``, dk and dv written per query head, as the kernel writes
    them (the caller sums the groups).  The f32 row statistics are 2 /
    head_dim of a tensor and are left out."""
    pairs = batch * heads * visible_pairs(seq, window)
    tensor = batch * seq * head_dim * 2             # bytes a head
    if backward:
        return (5 * 2.0 * pairs * head_dim,
                float(tensor * (5 * heads + 2 * kv_heads)))
    return (2 * 2.0 * pairs * head_dim,
            float(tensor * (2 * heads + 2 * kv_heads)))


def expert_products_cost(*, rows: int, d_model: int, d_ff: int,
                         experts: int):
    """(operations, bytes) a step of one sparse layer's grouped products
    on ``rows`` rows landing on ``experts`` held experts (bf16): three
    products a pass forward and again in the recompute, six backward
    (input and weight gradients); each pass reads the held experts' three
    matrices once, the backward writes their gradients; the rows' own
    traffic is counted with the dispatch, not here."""
    product = 2.0 * rows * d_model * d_ff
    weights = experts * 3 * d_model * d_ff * 2
    return 12 * product, float(4 * weights)


def optimizer_of(spec: dict):
    """The configuration's optimizer.  ``warmup_steps`` (this family's
    key) ramps the learning rate linearly from 0 to ``learning_rate`` over
    that many steps, as every recipe for a model of this kind does: at the
    full rate from step 0 the first update triples the loss and the router
    collapses within five steps (every token on the same experts), so the
    rows landing on this chip's experts, and the step time with them,
    become a seed's accident (PERF.md, PR 31)."""
    import optax

    spec = dict(spec)
    warmup = spec.pop("warmup_steps", 0)
    if warmup:
        spec["learning_rate"] = optax.linear_schedule(
            0.0, spec["learning_rate"], warmup)
    return make_optimizer(spec)


def build(config: dict, traffic: dict) -> Family:
    import jax

    from horovod_tpu.models import (config_from_published, transformer_init,
                                    transformer_loss)

    seq = traffic["seq"]
    cfg = config_from_published(
        config, layers=config["layers"], experts=config["experts"],
        experts_first=config["experts_first"], vocab=config["vocab"],
        router_score=config["router_score"], max_seq=seq,
        dtype=dtype_of(config["compute_dtype"]),
        param_dtype=dtype_of(config["param_dtype"]),
        remat=config["remat"] != "none",
        remat_policy=config["remat"] if config["remat"] != "none" else "full",
        loss_chunk=config["loss_chunk"])
    skew = traffic.get("token_skew", 1)

    def make_batch(key, samples):
        # floor(held rows * u^skew): ids drawn from the held slice of the
        # vocabulary, skewed so that there is something to learn.
        u = jax.random.uniform(key, (samples, seq))
        return ((cfg.vocab * u ** skew).astype("int32"),)

    return Family(
        init=lambda key: transformer_init(key, cfg),
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
