"""The ``granite_hybrid`` family: a published hybrid decoder LM (Granite
4.0-H: Mamba-2 state-space layers with a softmax-attention layer without a
position term among every few, a dense SwiGLU feed-forward in every layer,
four constant multipliers, a tied head) on one chip's share of its
deployment, through the repo's pattern model
(``horovod_tpu.models.config_from_published``) under next-token cross
entropy.  The configuration file keeps the source's own keys for every
width; ``layers`` and ``vocab`` (rows held) are the share.

Also here, because the per-layer readers of its cells use it: what the
chunked scan of a state-space layer needs a step, from shapes
(``ssd_scan_cost``).
"""

from __future__ import annotations

import functools

from benchmark.families import Family, dtype_of
from benchmark.families.laguna import optimizer_of, visible_pairs
from benchmark.reference import granite_hybrid as reference


def ssd_scan_macs(config: dict) -> float:
    """Forward multiply-adds a token of one state-space layer's chunked
    scan, C = ``mamba_chunk_size``: a group's row of the score matrix C
    B^T (C N, once for all its heads), a head's masked product with x (C
    P), and the build and the read of its state (2 P N)."""
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    n, chunk = config["mamba_d_state"], config["mamba_chunk_size"]
    return (config["mamba_n_groups"] * chunk * n
            + heads * (chunk * p + 2 * p * n))


def ssd_scan_cost(config: dict, *, tokens: int):
    """(operations, bytes) a step of ONE state-space layer's scan on
    ``tokens`` tokens, from shapes alone, whatever implements it.
    Operations: ``ssd_scan_macs`` forward, the same again in the
    recompute, twice that in the backward (each product's two operand
    gradients), 2 a multiply-add.  Bytes: a pass reads x (bf16, a head's P
    channels), B and C (bf16, a group's N each) and delta (f32, a head)
    and writes y (bf16), four passes' worth (forward, recompute, and the
    backward reads them and dy and writes their gradients); the
    chunk-boundary states (f32, tokens / C of them a head) are written by
    the forward and by the recompute and read by the backward."""
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    groups, n = config["mamba_n_groups"], config["mamba_d_state"]
    ops = 2.0 * 4 * ssd_scan_macs(config) * tokens
    a_pass = tokens * (2 * heads * p * 2 + 2 * groups * n * 2 + heads * 4)
    states = (tokens // config["mamba_chunk_size"]) * heads * p * n * 4
    return ops, float(4 * a_pass + 3 * states)


def layer_macs(config: dict, index: int, seq: int) -> dict:
    """Forward multiply-adds a token of layer ``index``, by part."""
    d = config["hidden_size"]
    if config["layer_types"][index] == "attention":
        h, hk = config["num_attention_heads"], config["num_key_value_heads"]
        dh = d // h
        out = {
            # wq, wk, wv, wo
            "projections": 2 * d * h * dh + 2 * d * hk * dh,
            # q.k and p.v over the causal half, averaged over the rows
            "scores": 2 * h * dh * visible_pairs(seq) / seq}
    else:
        heads = config["mamba_n_heads"]
        inner = heads * config["mamba_d_head"]
        conv = inner + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
        out = {
            # w_in ([z | xBC | dt]), w_out, and the convolution's taps
            "projections": (d * (inner + conv + heads) + inner * d
                            + config["mamba_d_conv"] * conv),
            "scan": ssd_scan_macs(config)}
    out["feed_forward"] = 3 * d * config["shared_intermediate_size"]
    return out


def flops_per_token(config: dict, seq: int) -> float:
    """Forward + backward matmul operations per token, from shapes: 2 per
    multiply-add, backward twice the forward, recompute not counted; the
    layers held here and the tied head over the held rows of the
    vocabulary, once (the embedding gather is no matmul)."""
    macs = sum(sum(layer_macs(config, i, seq).values())
               for i in range(config["layers"]))
    return 3.0 * 2.0 * (macs + config["hidden_size"] * config["vocab"])


def build(config: dict, traffic: dict) -> Family:
    import jax

    from horovod_tpu.models import (config_from_published, transformer_init,
                                    transformer_loss)

    seq = traffic["seq"]
    cfg = config_from_published(
        config, layers=config["layers"], vocab=config["vocab"], max_seq=seq,
        dtype=dtype_of(config["compute_dtype"]),
        param_dtype=dtype_of(config["param_dtype"]),
        remat=config["remat"] != "none",
        remat_policy=config["remat"] if config["remat"] != "none" else "full",
        loss_chunk=config["loss_chunk"])
    # A program that does not know the state-space kind must not run the
    # cell as some other model.
    held = [kind.ssm is not None for kind in
            cfg.leading + cfg.period * cfg.periods]
    if held != [kind == "mamba" for kind in
                config["layer_types"][:cfg.layers]]:
        raise ValueError("the configuration's mamba layers did not become "
                         "state-space layers of the program")
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
