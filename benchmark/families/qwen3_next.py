"""The ``qwen3_next`` family: a published hybrid decoder LM (three Gated
DeltaNet layers to one gated full-attention layer, every layer with a
softmax-routed sparse feed-forward and a gated shared expert, zero-centred
norms, an untied head) on one chip's share of its deployment, through the
repo's pattern model (``horovod_tpu.models.config_from_published``) under
next-token cross entropy.  The configuration file keeps the source's own
keys for every width; ``layers``, ``experts`` (held, from
``experts_first``) and ``vocab`` (rows held) are the share.

Also here, because the per-layer readers of its cells use it: what the
chunked scan of the linear layers needs a step, from shapes
(``scan_cost``).
"""

from __future__ import annotations

import functools

from benchmark.families import Family, dtype_of
from benchmark.families.laguna import optimizer_of, visible_pairs
from benchmark.reference import qwen3_next as reference

CHUNK = 64      # tokens a chunk of the scan (horovod_tpu.ops.gated_delta)


def is_full(config: dict, index: int) -> bool:
    return (index + 1) % config["full_attention_interval"] == 0


def scan_macs(config: dict, chunk: int = CHUNK) -> float:
    """Forward multiply-adds a token of one linear layer's chunked scan
    (from the L2 norms to O), C = ``chunk``: a key head's two pair products
    (k.k and q.k, C^2 dk each, shared by the value heads that read it); a
    value head's T (beta V), T (beta gamma K) and pairs x U (C^2 (dk + 2
    dv)), its three products with the state W S_0, Q S_0, K^T U (3 C dk
    dv) and the inverse of I + A by substitution (C^3 / 3); over C."""
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    c = chunk
    return (hk * 2 * c * c * dk
            + hv * (c * c * (dk + 2 * dv) + 3 * c * dk * dv + c ** 3 / 3)
            ) / c


def scan_cost(config: dict, *, tokens: int, chunk: int = CHUNK):
    """(operations, bytes) a step of ONE linear layer's scan on ``tokens``
    tokens.  Operations: ``scan_macs`` forward, the same again in the
    recompute, twice that in the backward (each product's two operand
    gradients), 2 a multiply-add.  Bytes: a pass reads q and k (bf16, key
    heads), v (bf16), g and beta (f32, a value head) and writes o (f32),
    four passes' worth (forward, recompute, and the backward reads them
    and dO and writes their gradients); the chunk-boundary states (f32,
    tokens / C of them a value head) are written by the recompute and
    read by the backward, and written once more by the forward."""
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    ops = 2.0 * 4 * scan_macs(config, chunk) * tokens
    a_pass = tokens * (2 * hk * dk * 2 + hv * dv * 2 + 2 * hv * 4
                       + hv * dv * 4)
    states = (tokens // chunk) * hv * dk * dv * 4
    return ops, float(4 * a_pass + 3 * states)


def layer_macs(config: dict, index: int, seq: int) -> dict:
    """Forward multiply-adds a token of layer ``index``, by part."""
    d = config["hidden_size"]
    if is_full(config, index):
        h, hk, dh = (config["num_attention_heads"],
                     config["num_key_value_heads"], config["head_dim"])
        out = {
            # wq (query and gate), wk, wv, wo
            "projections": d * h * dh * 2 + d * hk * dh * 2 + h * dh * d,
            # q.k and p.v over the causal half, averaged over the rows
            "scores": 2 * h * dh * visible_pairs(seq) / seq}
    else:
        hk, hv = (config["linear_num_key_heads"],
                  config["linear_num_value_heads"])
        kw = hk * config["linear_key_head_dim"]
        vw = hv * config["linear_value_head_dim"]
        out = {
            # w_qkvz, w_ba, w_out, and the convolution's taps
            "projections": (d * (2 * kw + 2 * vw) + d * 2 * hv + vw * d
                            + config["linear_conv_kernel_dim"]
                            * (2 * kw + vw)),
            "scan": scan_macs(config)}
    # The router over every routed expert; of a token's picks, experts /
    # num_experts land on held experts in expectation (0.625 at 10 x 32 of
    # 512); the shared expert and its gate.
    held = (config["num_experts_per_tok"] * config["experts"]
            / config["num_experts"])
    out["feed_forward"] = (
        d * config["num_experts"]
        + held * 3 * d * config["moe_intermediate_size"]
        + 3 * d * config["shared_expert_intermediate_size"] + d)
    return out


def flops_per_token(config: dict, seq: int) -> float:
    """Forward + backward matmul operations per token, from shapes: 2 per
    multiply-add, backward twice the forward, recompute not counted; the
    layers held here and the head over the held rows of the vocabulary."""
    macs = sum(sum(layer_macs(config, i, seq).values())
               for i in range(config["layers"]))
    return 3.0 * 2.0 * (macs + config["hidden_size"] * config["vocab"])


def build(config: dict, traffic: dict) -> Family:
    import jax

    from horovod_tpu.models import (config_from_published, transformer_init,
                                    transformer_loss)

    seq = traffic["seq"]
    cfg = config_from_published(
        config, layers=config["layers"], experts=config["experts"],
        experts_first=config["experts_first"], vocab=config["vocab"],
        router_score=config["router_score"],
        shared_gate=config["shared_expert_gate"],
        out_gate=config["attn_output_gate"], qk_norm=config["qk_norm"],
        zero_centered_norm=config["zero_centered_norm"], max_seq=seq,
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
