"""The ``transformer_lm`` family: the repo's decoder LM
(``horovod_tpu.models.transformer``) under next-token cross entropy."""

from __future__ import annotations

import functools

from benchmark.families import Family, dtype_of, make_optimizer
from benchmark.reference import transformer_lm as reference


def flops_per_token(config: dict, seq: int) -> float:
    """Forward + backward matmul operations per token, from shapes: 2 per
    multiply-add, backward twice the forward, recompute not counted.
    Projections and the three SwiGLU matrices per layer, the tied output
    head once (the embedding gather is no matmul), and the score and value
    products over the causal half of the square only."""
    d, f, h, hk = (config["d_model"], config["d_ff"], config["heads"],
                   config["kv_heads"])
    dh = d // h
    proj = d * (2 * h * dh + 2 * hk * dh)          # wq, wo, wk, wv
    mlp = 3 * d * f
    attn = 2 * (seq / 2) * h * dh                  # q.k and p.v, causal half
    forward = 2 * (config["layers"] * (proj + mlp + attn)
                   + d * config["vocab"])
    return 3.0 * forward


def build(config: dict, traffic: dict) -> Family:
    import jax

    from horovod_tpu.models import (TransformerConfig, transformer_init,
                                    transformer_loss)

    seq = traffic["seq"]
    cfg = TransformerConfig(
        vocab=config["vocab"], layers=config["layers"],
        d_model=config["d_model"], heads=config["heads"],
        kv_heads=config["kv_heads"], d_ff=config["d_ff"], max_seq=seq,
        rope_theta=config["rope_theta"],
        dtype=dtype_of(config["compute_dtype"]),
        param_dtype=dtype_of(config["param_dtype"]),
        remat=config["remat"] != "none",
        remat_policy=config["remat"] if config["remat"] != "none" else "full",
        loss_chunk=config["loss_chunk"])

    skew = traffic.get("token_skew", 1)

    def make_batch(key, samples):
        # floor(vocab * u^skew): a Zipf-like unigram skew, so there is
        # something to learn in a batch seen for the first time.  Uniform
        # tokens (skew 1) leave the loss flat until a batch repeats.
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
        sample_size=traffic.get("sample_sequences", 2),
        reference_loss=functools.partial(
            reference.loss, heads=cfg.heads, rope_theta=cfg.rope_theta,
            checkpoint_layers=traffic.get("reference_checkpoint_layers",
                                          False)),
        # The sample's two sequences are too few for `auto` to choose the
        # kernel by itself, so the sample is pinned to the path the step
        # program was seen to take.
        sample_env=lambda mosaic: {
            "HVDT_FLASH_ATTENTION": "on" if mosaic else "off"},
        tolerances=config["tolerances"])
