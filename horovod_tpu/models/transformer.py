"""Flagship decoder-only Transformer LM, built for the 5-axis mesh.

TPU-first design decisions:

* **bf16 compute, f32 params/accumulation** — MXU-native (SURVEY.md §6's
  per-chip throughput target is set by MXU utilization).  With
  ``HVDT_FP8=matmul`` the MLP and attention projections drop to
  per-tensor-scaled e4m3 operands (quant/fp8.py) where the backend
  supports the fp8 convert-dot; accumulation stays f32.
* **RoPE** instead of learned positions — no position table to shard.
* **Scan over layers** — one compiled block body regardless of depth
  (compile time O(1) in layers), standard XLA practice.
* **Hybrid parallelism**: dp/fsdp/tp are expressed with logical-axis
  sharding rules (GSPMD auto-partitioning inserts the collectives); sp
  (ring attention) and ep (MoE alltoall) are manual ``shard_map`` islands;
  pp wraps the block stack in ``pipeline_1f1b``.

The reference has no model layer — its examples lean on torchvision/Keras
(ref: examples/pytorch/pytorch_synthetic_benchmark.py:17-26).  This module
is the equivalent benchmark substrate plus the TP/SP/PP/EP showcase the
reference lacks (SURVEY.md §2.7).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.attention import attention
from ..ops.eva import eva_attention, eva_visible_pairs
from ..ops.gated_delta import gated_delta_net, scan_macs_per_token
from ..ops.pallas_kernels import rope
from ..ops.short_conv import gated_short_conv
from ..ops.ssd import mamba2_mixer
from ..ops.ssd import scan_macs_per_token as ssd_macs_per_token
from ..parallel.moe import (ROUTE_SAVED, moe_dispatch_combine,
                            moe_held_experts)
from ..parallel.pipeline import pipeline_1f1b
from ..parallel.ring_attention import ring_attention
from ..quant import fp8 as _fp8

__all__ = [
    "TransformerConfig", "LayerKind", "LinearMixer", "StateSpaceMixer",
    "ShortConv", "Eva", "Rope", "Experts",
    "config_from_published", "transformer_init", "transformer_apply",
    "transformer_loss", "transformer_block_diffusion_loss",
    "block_diffusion_corrupt", "transformer_logical_axes",
    "transformer_flops_per_token", "remat_from_env", "checkpoint_policy",
    "transformer_decode_paged", "transformer_prefill_paged",
    "transformer_prefill_collect",
]


@dataclasses.dataclass(frozen=True)
class Rope:
    """Rotary settings of one kind of layer.  ``dim`` is how many of each
    head's dimensions rotate (0 = all of them; the rest pass through);
    ``yarn_factor`` > 0 blends interpolated and extrapolated frequencies
    as YaRN does, static in the sequence length; ``attention_factor``
    multiplies cos and sin."""
    theta: float = 10000.0
    dim: int = 0
    yarn_factor: float = 0.0
    yarn_original_max: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class LinearMixer:
    """The sizes of a Gated DeltaNet mixer (``ops/gated_delta.py``):
    ``key_heads`` query / key heads of ``key_dim``, ``value_heads`` value
    heads of ``value_dim`` (value head n reads key head n // (value_heads
    / key_heads)), a causal depthwise convolution of ``conv`` taps over
    the q, k and v channels."""
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv: int = 4

    @property
    def key_width(self) -> int:
        return self.key_heads * self.key_dim

    @property
    def value_width(self) -> int:
        return self.value_heads * self.value_dim

    @property
    def sizes(self) -> Dict[str, int]:
        """The head counts and sizes, as ``ops.gated_delta`` names them."""
        return dict(key_heads=self.key_heads, value_heads=self.value_heads,
                    key_dim=self.key_dim, value_dim=self.value_dim)


@dataclasses.dataclass(frozen=True)
class StateSpaceMixer:
    """The sizes of a Mamba-2 mixer (``ops/ssd.py``): ``heads`` heads of
    ``head_dim`` over a state of ``state``, B and C shared by the heads of
    each of ``groups`` groups, a causal depthwise convolution of ``conv``
    taps (with a bias where ``conv_bias``) over the x, B and C channels,
    the scan in chunks of ``chunk`` tokens."""
    heads: int
    head_dim: int
    state: int
    groups: int = 1
    conv: int = 4
    conv_bias: bool = True
    chunk: int = 256

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_width(self) -> int:
        return self.inner + 2 * self.groups * self.state

    @property
    def sizes(self) -> Dict[str, int]:
        """The head counts and sizes, as ``ops.ssd`` names them."""
        return dict(heads=self.heads, head_dim=self.head_dim,
                    state=self.state, groups=self.groups, chunk=self.chunk)


@dataclasses.dataclass(frozen=True)
class ShortConv:
    """The sizes of a double-gated short convolution
    (``ops/short_conv.py``): a causal depthwise convolution of ``taps``
    taps (with a bias where ``bias``) over the model's own width, between
    two elementwise gates."""
    taps: int = 3
    bias: bool = False


@dataclasses.dataclass(frozen=True)
class Eva:
    """The sizes of EVA attention (``ops/eva.py``): exact softmax inside
    aligned windows of ``window`` positions, joined in one softmax with a
    learned summary of every ``chunk`` positions of every earlier window.
    A head's two learned vectors start N(0, 1) clipped to +-1, times
    ``init_std``."""
    window: int
    chunk: int
    init_std: float = 0.02


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """One kind of layer of a pattern: its mixer (softmax attention with
    its own head counts, window and rotary settings, with ``eva`` set under
    EVA's two masks in place of ``window``'s, with ``rope`` None without any
    position term; or, with ``linear`` set, the Gated DeltaNet of those
    sizes, with ``ssm`` the Mamba-2 mixer of those, or with ``conv`` the
    double-gated short convolution, which read none of the attention
    fields) and its feed-forward (dense SwiGLU of width
    ``d_ff``, or with ``sparse`` the configuration's expert layer,
    ``TransformerConfig.moe``).
    ``heads`` / ``kv_heads`` are the heads held here: heads ``heads_first
    .. heads_first + heads - 1`` of the model's where this is one chip's
    share of a layer whose attention is divided by heads (no head's
    arithmetic reads its index, so the field only says which they are)."""
    heads: int
    kv_heads: int
    d_ff: int = 0
    window: Optional[int] = None     # query i sees keys i - window < j <= i
    rope: Optional[Rope] = Rope()
    sparse: bool = False
    linear: Optional[LinearMixer] = None
    eva: Optional[Eva] = None
    ssm: Optional[StateSpaceMixer] = None
    heads_first: int = 0
    conv: Optional[ShortConv] = None


@dataclasses.dataclass(frozen=True)
class Experts:
    """The expert layer of a configuration: ``held`` experts of width
    ``d_ff`` live here, experts ``first .. first + held - 1`` of the
    ``routed`` the router scores (0 = the held ones are all there are);
    a token picks ``per_token``; see ``parallel.moe.moe_held_experts``.
    With ``select_bias`` a layer carries ``router_bias`` [routed], float32:
    the picks are the largest of score + bias, the weights the scores at
    them without it; ``normalize_eps`` > 0 is added to the picked scores'
    sum before they are divided by it."""
    held: int
    d_ff: int
    routed: int = 0
    per_token: int = 1
    first: int = 0
    score: str = "sigmoid"           # or "softmax"
    normalize: bool = True           # picked scores divided by their sum
    scale: float = 1.0
    gated: bool = True               # SwiGLU experts (else silu(x W_up) W_down)
    shared_d_ff: int = 0             # > 0: a shared expert of this width
    shared_gate: bool = False        # its output times sigmoid(x . ws_sg)
    select_bias: bool = False
    normalize_eps: float = 0.0


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """One block repeated ``layers`` times (the uniform configuration: the
    fields down to ``loss_chunk``), or, with ``period`` set, a PATTERN of
    layers: ``leading`` layers, each of its own kind, then the kinds of
    ``period`` in turn, repeated to ``layers`` in all."""
    vocab: int = 32000
    layers: int = 4
    d_model: int = 512
    heads: int = 8
    kv_heads: int = 8            # < heads ⇒ GQA
    d_ff: int = 2048
    max_seq: int = 2048
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16    # activation/compute dtype
    param_dtype: Any = jnp.float32
    # MoE: num_experts == 0 ⇒ dense MLP.  Every block is MoE when on
    # (simplest uniform scan body; interleaving is a config refinement).
    num_experts: int = 0
    capacity_factor: float = 1.25
    # Parallel degrees the *model code* must know about (mesh axes the
    # forward pass opens manual islands for); dp/fsdp/tp stay automatic.
    sp: int = 1                  # sequence-parallel degree (ring attention)
    ep: int = 1                  # expert-parallel degree
    pp: int = 1                  # pipeline stages (layers % pp == 0)
    remat: bool = False          # jax.checkpoint each block
    # Rematerialization policy when remat=True:
    #   "full" — save block inputs and recompute the rest (min HBM,
    #            +1/3 FLOPs — the classic trade);
    #   "dots" — jax.checkpoint_policies.dots_with_no_batch_dims_saveable:
    #            save non-batched matmul outputs (projections, FF), so
    #            the backward recomputes only cheap elementwise work and
    #            attention scores.  ~MXU-free recompute at the cost of
    #            O(layers * 6*b*l*d + b*l*4d) extra HBM residency.
    # Under either, a sparse layer also keeps what its expert layer names
    # ``hvdt_moe_route`` (parallel/moe.py ROUTE_SAVED: the picks, their
    # scores, the sort by expert and the rows' weights; about 25 bytes a
    # pick, 4 MB a layer at 163,840 picks beside a [T, D] input of 67 MB),
    # so the recompute runs no top-k, no sort and no router product.  A
    # layer without an expert layer names nothing and saves what the bare
    # policy saves.
    # (An "attn" policy saving each block's attention output was measured
    # and REMOVED: saving attention's output cannot skip recomputing its
    # internals — the VJP still needs q/k/v/scores — so it bought 1.3%
    # of grad FLOPs for ~3.2 GB extra residency and OOM'd the BERT-Large
    # bs128 config.)
    remat_policy: str = "full"
    loss_chunk: int = 0          # >0: chunked-vocab cross entropy
    head_dim: int = 0            # 0: d_model // heads
    # A pattern of layers (period non-empty).  Leading layers are unrolled;
    # inside a period, neighbours of one kind are scanned together (one
    # compiled body, one set of kernel call sites), and the periods are
    # scanned.  ``heads`` / ``kv_heads`` / ``d_ff`` / ``rope_theta`` /
    # ``num_experts`` above are the uniform configuration's and unused here.
    leading: Tuple[LayerKind, ...] = ()
    period: Tuple[LayerKind, ...] = ()
    moe: Optional[Experts] = None    # the expert layer of ``sparse`` kinds
    # A sigmoid gate on attention's output, from the layer's normed input:
    # "" none; "head" one a head, from a matrix of its own (``wg`` [d,
    # heads]); "elementwise" one a dimension, from the query projection's
    # second half (``wq`` [d, heads x 2 head_dim], a head's columns its
    # query then its gate).  True / False read as "head" / "".
    out_gate: str = ""
    tie_head: bool = True        # False: an output matrix of its own, "head"
    qk_norm: bool = False        # q and k RMS-normed a head before RoPE
    # Every RMSNorm but the linear mixer's gated one scales by 1 + gain,
    # the gain initialised 0 (else by the gain, initialised 1).
    zero_centered_norm: bool = False
    # The objective.  0: next-token cross entropy under a causal mask
    # (``transformer_loss``).  B > 0: diffusion over blocks of B tokens
    # (``transformer_block_diffusion_loss``): the model runs on the 2 L
    # rows [noisy ; clean] of a sequence of L tokens, both streams at
    # positions 0 .. L-1, under ``ops.pallas_kernels.block_diffusion_mask``;
    # the mask token is the last held row of the vocabulary.
    diffusion_block: int = 0
    # Prediction heads a row: head n of row i predicts token i + 1 + n, all
    # from one output matrix [pred_heads x vocab, d_model] (untied), the
    # loss the mean over every (row, head) pair that has a target.  With
    # more than one, ``loss_chunk`` counts ROWS a chunk (the logits of all
    # the heads of a chunk of rows exist at a time), not vocabulary rows.
    pred_heads: int = 1
    norm_eps: float = 1e-6       # every RMSNorm's epsilon
    # Constants some published models scale by (Granite's four).  At these
    # defaults none of them is an operation of the program.
    embedding_multiplier: float = 1.0    # the embedded rows times it
    residual_multiplier: float = 1.0     # each sublayer's output times it
    attention_multiplier: float = 0.0    # the score scale; 0: head_dim^-0.5
    logits_scaling: float = 1.0          # the logits divided by it

    def __post_init__(self):
        if isinstance(self.out_gate, bool):
            object.__setattr__(self, "out_gate",
                               "head" if self.out_gate else "")
        if self.out_gate not in ("", "head", "elementwise"):
            raise ValueError(
                f"out_gate={self.out_gate!r}: '', 'head' or 'elementwise'")
        if not self.head_dim:
            object.__setattr__(self, "head_dim", self.d_model // self.heads)
        if self.diffusion_block and (
                self.sp > 1 or self.pp > 1 or any(
                    k.window or k.linear or k.eva or k.ssm or k.conv
                    for k in self.leading + self.period)):
            raise ValueError(
                "diffusion over blocks runs full softmax attention with sp "
                "= pp = 1 (the block mask has no window, and a recurrent "
                "mixer would carry the noisy stream into the clean one)")
        if self.pred_heads > 1 and (self.tie_head or self.diffusion_block):
            raise ValueError(
                "several prediction heads take an output matrix of their "
                "own (tie_head=False) under the next-token objective")
        if self.period:
            repeated = self.layers - len(self.leading)
            if repeated <= 0 or repeated % len(self.period):
                raise ValueError(
                    f"layers={self.layers} is not {len(self.leading)} "
                    f"leading layers plus whole periods of "
                    f"{len(self.period)}")
            if self.sp > 1 or self.ep > 1 or self.pp > 1:
                raise ValueError(
                    "a layer-pattern configuration runs with sp = ep = pp "
                    "= 1 (the manual islands take uniform configurations)")
            if any(k.sparse for k in self.leading + self.period) \
                    and self.moe is None:
                raise ValueError("a sparse layer kind needs cfg.moe")
        elif self.leading:
            raise ValueError("leading layers come before a period")

    @property
    def uniform_kind(self) -> LayerKind:
        """The uniform configuration's one block as a kind."""
        return LayerKind(heads=self.heads, kv_heads=self.kv_heads,
                         d_ff=self.d_ff, rope=Rope(theta=self.rope_theta),
                         sparse=bool(self.num_experts))

    @property
    def experts(self) -> Optional[Experts]:
        """The expert layer's settings: ``moe``, or what the uniform
        configuration's ``num_experts`` has always meant on one device
        (softmax scores, one pick weighted by its score, every expert
        held, two matrices an expert)."""
        if self.moe is not None or not self.num_experts:
            return self.moe
        return Experts(held=self.num_experts, d_ff=self.d_ff,
                       score="softmax", normalize=False, gated=False)

    @property
    def period_runs(self) -> Tuple[Tuple[LayerKind, int], ...]:
        """The period as runs of equal neighbours: ((kind, count), ...)."""
        runs = []
        for kind in self.period:
            if runs and runs[-1][0] == kind:
                runs[-1][1] += 1
            else:
                runs.append([kind, 1])
        return tuple((k, n) for k, n in runs)

    @property
    def periods(self) -> int:
        return ((self.layers - len(self.leading)) // len(self.period)
                if self.period else 0)

    @property
    def layers_per_stage(self) -> int:
        assert self.layers % max(self.pp, 1) == 0
        return self.layers // max(self.pp, 1)


def config_from_published(published: Dict[str, Any], *,
                          layers: Optional[int] = None,
                          layers_first: int = 0,
                          experts: Optional[int] = None,
                          experts_first: int = 0,
                          vocab: Optional[int] = None,
                          heads: Optional[int] = None,
                          heads_first: int = 0,
                          router_score: str = "sigmoid",
                          shared_gate: bool = False,
                          normalize_eps: float = 0.0,
                          **fields) -> TransformerConfig:
    """A pattern configuration from a model's published settings, cut to
    this device's share of it.

    ``published`` holds the keys of the model's ``config.json`` under their
    own names: ``hidden_size``, ``head_dim``, ``num_attention_heads`` or
    ``num_attention_heads_per_layer``, ``num_key_value_heads``,
    ``layer_types`` (``full_attention`` or ``attention`` /
    ``sliding_attention``, with ``sliding_window`` / ``linear_attention``,
    a Gated DeltaNet of ``linear_num_key_heads``,
    ``linear_num_value_heads``, ``linear_key_head_dim``,
    ``linear_value_head_dim``, ``linear_conv_kernel_dim`` / ``mamba``, a
    Mamba-2 mixer of ``mamba_n_heads``, ``mamba_d_head``,
    ``mamba_d_state``, ``mamba_n_groups``, ``mamba_d_conv``,
    ``mamba_conv_bias``, ``mamba_chunk_size`` / ``conv``, a double-gated
    short convolution of ``conv_L_cache`` taps with ``conv_bias``; any
    other entry is refused) or ``full_attention_interval`` (every
    n-th layer full attention, the others linear, as ``transformers``
    derives ``layer_types`` from it), ``rope_parameters`` (by layer type,
    or one group) or ``rope_theta`` and ``partial_rotary_factor`` at top
    level, ``position_embedding_type`` (``rope``, or ``nope``: attention
    without a position term), ``mlp_layer_types`` or ``num_dense_layers``
    (that many leading layers dense, the rest sparse) (``dense`` of
    ``shared_intermediate_size``, else of ``intermediate_size`` /
    ``sparse``: ``num_experts`` of ``moe_intermediate_size``,
    ``num_experts_per_tok`` picked, ``norm_topk_prob``,
    ``moe_routed_scaling_factor`` or ``routed_scaling_factor``,
    ``use_expert_bias`` (a selection bias, ``Experts.select_bias``), a
    shared expert of ``shared_expert_intermediate_size``), ``gating``,
    ``tie_word_embeddings``, ``vocab_size``, ``num_hidden_layers``;
    ``attention_class`` (``eva``: every attention layer is EVA attention of
    ``window_size`` and ``chunk_size``, its learned vectors started at
    ``init_std``), ``num_pred_heads`` (prediction heads a row),
    ``norm_add_unit_offset`` (every RMSNorm scales by 1 + gain),
    ``rms_norm_eps`` or ``norm_eps``, and the four constants
    ``embedding_multiplier``,
    ``residual_multiplier``, ``attention_multiplier`` (the score scale)
    and ``logits_scaling`` (the logits' divisor).  No width is an
    argument.  The cut: ``layers`` layers from layer ``layers_first`` on
    (what is left of the leading ones there and at least a period),
    ``experts``
    of each sparse layer's experts from ``experts_first`` on (the router
    keeps its width), the first ``vocab`` rows of the vocabulary, ``heads``
    of each attention layer's query heads from ``heads_first`` on with
    their share of the key heads (attention divided by heads: wq, wk, wv
    by columns, wo by rows; what the absent heads would add to the
    sublayer's output is left out).  ``router_score``,
    ``shared_gate`` (the shared expert's sigmoid gate) and
    ``normalize_eps`` (``Experts.normalize_eps``) are what
    ``config.json`` leaves to modelling code, as are the ``fields``
    ``out_gate``, ``qk_norm``, ``zero_centered_norm`` and
    ``diffusion_block`` (the objective: a model trained by diffusion over
    blocks publishes the network of its autoregressive parent); ``fields``
    are further ``TransformerConfig`` fields (``max_seq``, ``dtype``,
    ``remat``, ...).
    """
    c = published
    depth = c["num_hidden_layers"]
    head_dim = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    published_heads = c.get("num_attention_heads_per_layer") or \
        [c["num_attention_heads"]] * depth
    eva = Eva(window=c["window_size"], chunk=c["chunk_size"],
              init_std=float(c.get("init_std", 0.02))) \
        if c.get("attention_class") == "eva" else None
    interval = c.get("full_attention_interval")
    layer_types = c.get("layer_types") or [
        "linear_attention" if interval and (i + 1) % interval
        else "full_attention" for i in range(depth)]
    known = ("full_attention", "attention", "sliding_attention",
             "linear_attention", "mamba", "conv")
    unknown = sorted(set(layer_types) - set(known))
    if unknown:
        raise ValueError(f"layer_types holds {unknown}: one of {known}")
    positions = c.get("position_embedding_type", "rope")
    if positions not in ("rope", "nope"):
        raise ValueError(
            f"position_embedding_type={positions!r}: 'rope' or 'nope'")
    for unbuilt in ("num_local_experts", "mamba_proj_bias"):
        # experts beside the shared feed-forward; a bias on w_in and w_out
        if c.get(unbuilt):
            raise ValueError(f"{unbuilt}={c[unbuilt]!r} is not built yet")
    dense_first = c.get("num_dense_layers", 0)
    mlp_types = c.get("mlp_layer_types") or \
        ["dense"] * dense_first + ["sparse" if c.get("num_experts")
                                   else "dense"] * (depth - dense_first)
    dense_width = c.get("shared_intermediate_size") or \
        c.get("intermediate_size")
    ropes = c.get("rope_parameters") or {
        "rope_theta": c["rope_theta"],
        "partial_rotary_factor": c.get("partial_rotary_factor", 1)}
    linear = LinearMixer(
        key_heads=c["linear_num_key_heads"],
        value_heads=c["linear_num_value_heads"],
        key_dim=c["linear_key_head_dim"],
        value_dim=c["linear_value_head_dim"],
        conv=c["linear_conv_kernel_dim"]) \
        if "linear_attention" in layer_types else None
    ssm = StateSpaceMixer(
        heads=c["mamba_n_heads"], head_dim=c["mamba_d_head"],
        state=c["mamba_d_state"], groups=c["mamba_n_groups"],
        conv=c["mamba_d_conv"], conv_bias=bool(c["mamba_conv_bias"]),
        chunk=c["mamba_chunk_size"]) if "mamba" in layer_types else None
    conv = ShortConv(taps=c["conv_L_cache"],
                     bias=bool(c.get("conv_bias", False))) \
        if "conv" in layer_types else None

    def rope_of(layer_type: str) -> Optional[Rope]:
        if positions == "nope":
            return None
        r = ropes.get(layer_type, ropes)
        rotated = int(head_dim * r.get("partial_rotary_factor", 1))
        yarn = r.get("rope_type", "default") == "yarn"
        return Rope(
            theta=float(r["rope_theta"]),
            dim=0 if rotated == head_dim else rotated,
            yarn_factor=float(r["factor"]) if yarn else 0.0,
            yarn_original_max=r.get("original_max_position_embeddings", 0)
            if yarn else 0,
            yarn_beta_fast=float(r.get("beta_fast", 32)),
            yarn_beta_slow=float(r.get("beta_slow", 1)),
            attention_factor=float(r.get(
                "attention_factor",
                0.1 * math.log(r["factor"]) + 1.0 if yarn else 1.0)))

    def kind_of(i: int) -> LayerKind:
        feed_forward = dict(
            d_ff=0 if mlp_types[i] == "sparse" else dense_width,
            sparse=mlp_types[i] == "sparse")
        if layer_types[i] == "linear_attention":
            return LayerKind(heads=0, kv_heads=0, linear=linear,
                             **feed_forward)
        if layer_types[i] == "mamba":
            return LayerKind(heads=0, kv_heads=0, ssm=ssm, **feed_forward)
        if layer_types[i] == "conv":
            return LayerKind(heads=0, kv_heads=0, conv=conv, **feed_forward)
        held = heads or published_heads[i]
        kv_held, ragged = divmod(c["num_key_value_heads"] * held,
                                 published_heads[i])
        if ragged or not kv_held:
            raise ValueError(
                f"heads={held} of {published_heads[i]} is no whole share of "
                f"the {c['num_key_value_heads']} key heads")
        return LayerKind(
            heads=held, kv_heads=kv_held,
            window=c["sliding_window"]
            if layer_types[i] == "sliding_attention" else None,
            rope=rope_of(layer_types[i]), eva=eva,
            heads_first=heads_first if heads else 0, **feed_forward)

    kinds = [kind_of(i) for i in range(depth)]
    # The fewest leading layers after which the published stack repeats
    # (at least twice), and its shortest period.
    lead, period = next(
        (n, p) for n in range(depth) for p in range(1, (depth - n) // 2 + 1)
        if all(kinds[i] == kinds[i + p] for i in range(n, depth - p)))
    layers = layers or depth - layers_first
    if layers_first < 0 or layers_first + layers > depth:
        raise ValueError(
            f"layers {layers_first} .. {layers_first + layers - 1} of "
            f"{depth}")
    # The held range's own leading layers: what it holds of the stack's.
    # Whole periods only: what does not fill one goes to the leading layers
    # (the whole stack of 1 + 39 layers at period 4 is 4 leading + 9 x 4).
    lead = max(lead - layers_first, 0)
    lead += (layers - lead) % period
    if layers - lead < period:
        raise ValueError(
            f"layers={layers} from layer {layers_first}: keep the {lead} "
            f"leading layers and at least one whole period of {period} "
            "after them")
    kinds = kinds[layers_first:layers_first + layers]
    moe = None
    if any(k.sparse for k in kinds):
        routed = c["num_experts"]
        moe = Experts(
            held=experts or routed, d_ff=c["moe_intermediate_size"],
            routed=routed, per_token=c["num_experts_per_tok"],
            first=experts_first, score=router_score,
            normalize=bool(c.get("norm_topk_prob", True)),
            scale=float(c.get("moe_routed_scaling_factor",
                              c.get("routed_scaling_factor", 1.0))),
            shared_d_ff=c.get("shared_expert_intermediate_size", 0),
            shared_gate=shared_gate,
            select_bias=bool(c.get("use_expert_bias", False)),
            normalize_eps=normalize_eps)
    fields.setdefault("out_gate", "head" if c.get("gating") else "")
    fields.setdefault("pred_heads", c.get("num_pred_heads", 1))
    fields.setdefault("norm_eps", float(
        c.get("rms_norm_eps", c.get("norm_eps", 1e-6))))
    for constant in ("embedding_multiplier", "residual_multiplier",
                     "attention_multiplier", "logits_scaling"):
        if constant in c:
            fields.setdefault(constant, float(c[constant]))
    if c.get("norm_add_unit_offset"):
        fields.setdefault("zero_centered_norm", True)
    return TransformerConfig(
        vocab=vocab or c["vocab_size"], layers=layers,
        d_model=c["hidden_size"], head_dim=head_dim,
        leading=tuple(kinds[:lead]), period=tuple(kinds[lead:lead + period]),
        moe=moe,
        tie_head=bool(c.get("tie_word_embeddings", True)), **fields)


def _init_linear(key, fan_in, shape, dtype):
    return (jax.random.normal(key, shape) * (fan_in ** -0.5)).astype(dtype)


def _norm_gain(cfg: TransformerConfig, shape) -> jax.Array:
    """A norm's gain as initialised: 1, or 0 where the norm scales by
    1 + gain."""
    return (jnp.zeros if cfg.zero_centered_norm else jnp.ones)(
        shape, cfg.param_dtype)


def _init_linear_mixer(ks, cfg: TransformerConfig, m: LinearMixer) -> Dict:
    """The Gated DeltaNet's leaves (``ops.gated_delta.gated_delta_net``
    says what each is).  ``a_log`` = log(u), u uniform in [1e-3, 16), and
    ``dt_bias`` = 1, as the published model initialises them."""
    d, pd = cfg.d_model, cfg.param_dtype
    kw, vw = m.key_width, m.value_width
    return {
        "w_qkvz": _init_linear(next(ks), d, (d, 2 * kw + 2 * vw), pd),
        "w_ba": _init_linear(next(ks), d, (d, 2 * m.value_heads), pd),
        "conv": _init_linear(next(ks), m.conv, (m.conv, 2 * kw + vw), pd),
        "a_log": jnp.log(jax.random.uniform(
            next(ks), (m.value_heads,), minval=1e-3, maxval=16.0)
        ).astype(pd),
        "dt_bias": jnp.ones((m.value_heads,), pd),
        "gdn_norm": jnp.ones((m.value_dim,), pd),
        "w_out": _init_linear(next(ks), vw, (vw, d), pd),
    }


def _init_taps(key, taps: int, shape, dtype):
    """A depthwise convolution's taps or its bias: uniform in
    +-taps^-0.5, torch's Conv1d default at one channel a group."""
    bound = taps ** -0.5
    return jax.random.uniform(key, shape, minval=-bound,
                              maxval=bound).astype(dtype)


def _init_state_space_mixer(ks, cfg: TransformerConfig,
                            m: StateSpaceMixer) -> Dict:
    """The Mamba-2 mixer's leaves (``ops.ssd.mamba2_mixer`` says what each
    is).  ``a_log`` = log(1 .. heads), ``d_skip`` = 1, the convolution's
    taps and bias uniform in +-taps^-0.5, as the published model's code
    initialises them; ``dt_bias`` the inverse softplus of a time step
    drawn log-uniform in [1e-3, 1e-1], as the Mamba-2 code draws it
    (arXiv:2405.21060), so that a head's state lives for tens to
    thousands of tokens and not for one."""
    d, pd = cfg.d_model, cfg.param_dtype
    lo, hi = math.log(1e-3), math.log(1e-1)
    p = {
        "w_in": _init_linear(next(ks), d,
                             (d, m.inner + m.conv_width + m.heads), pd),
        "conv": _init_taps(next(ks), m.conv, (m.conv, m.conv_width), pd),
        "a_log": jnp.log(jnp.arange(1, m.heads + 1, dtype=jnp.float32)
                         ).astype(pd),
        "d_skip": jnp.ones((m.heads,), pd),
        "ssd_norm": jnp.ones((m.inner,), pd),
        "w_out": _init_linear(next(ks), m.inner, (m.inner, d), pd),
    }
    if m.conv_bias:
        p["conv_bias"] = _init_taps(next(ks), m.conv, (m.conv_width,), pd)
    step = jnp.exp(jax.random.uniform(next(ks), (m.heads,), minval=lo,
                                      maxval=hi))
    # softplus(dt_bias) = step
    p["dt_bias"] = (step + jnp.log(-jnp.expm1(-step))).astype(pd)
    return p


def _init_short_conv(ks, cfg: TransformerConfig, m: ShortConv) -> Dict:
    """The double-gated short convolution's leaves
    (``ops.short_conv.gated_short_conv`` says what each is): the taps and
    the bias as the published constructor's Conv1d draws them."""
    d, pd = cfg.d_model, cfg.param_dtype
    p = {"w_in": _init_linear(next(ks), d, (d, 3 * d), pd),
         "conv": _init_taps(next(ks), m.taps, (m.taps, d), pd),
         "w_out": _init_linear(next(ks), d, (d, d), pd)}
    if m.bias:
        p["conv_bias"] = _init_taps(next(ks), m.taps, (d,), pd)
    return p


def _init_layer(key, cfg: TransformerConfig, kind: LayerKind) -> Dict:
    """One layer of a pattern: its mixer (attention of ``kind``'s sizes,
    with ``wg`` or a query projection twice as wide where the output is
    gated and ``q_norm`` / ``k_norm`` where q and k are normed; or the
    linear, the state-space or the short-convolution mixer's leaves) and
    its dense or sparse feed-forward (with ``router_bias``, zeros, where
    the picks take a selection bias: the published constructor's)."""
    d, dh, pd = cfg.d_model, cfg.head_dim, cfg.param_dtype
    h, hk = kind.heads, kind.kv_heads
    # Twelve keys serve every layer from before the shared expert's gate;
    # the further ones are drawn apart, so that those layers' trees stay.
    ks = iter((*jax.random.split(key, 12),
               *jax.random.split(jax.random.fold_in(key, 12), 4)))
    p = {"ln1": _norm_gain(cfg, (d,)), "ln2": _norm_gain(cfg, (d,))}
    if kind.linear is not None:
        p.update(_init_linear_mixer(ks, cfg, kind.linear))
    elif kind.ssm is not None:
        p.update(_init_state_space_mixer(ks, cfg, kind.ssm))
    elif kind.conv is not None:
        p.update(_init_short_conv(ks, cfg, kind.conv))
    else:
        wide = 2 if cfg.out_gate == "elementwise" else 1
        p.update(
            wq=_init_linear(next(ks), d, (d, h * dh * wide), pd),
            wk=_init_linear(next(ks), d, (d, hk * dh), pd),
            wv=_init_linear(next(ks), d, (d, hk * dh), pd),
            wo=_init_linear(next(ks), h * dh, (h * dh, d), pd))
        if cfg.out_gate == "head":
            p["wg"] = _init_linear(next(ks), d, (d, h), pd)
        if cfg.qk_norm:
            p["q_norm"] = _norm_gain(cfg, (dh,))
            p["k_norm"] = _norm_gain(cfg, (dh,))
        if kind.eva is not None:
            for name in ("phi", "mu"):
                p[name] = (jnp.clip(jax.random.normal(next(ks), (h, dh)),
                                    -1.0, 1.0) * kind.eva.init_std
                           ).astype(pd)
    if not kind.sparse:
        f = kind.d_ff
        p["w_up"] = _init_linear(next(ks), d, (d, f), pd)
        p["w_gate"] = _init_linear(next(ks), d, (d, f), pd)
        p["w_down"] = _init_linear(next(ks), f, (f, d), pd)
        return p
    moe = cfg.moe
    e, f = moe.held, moe.d_ff
    p["w_router"] = _init_linear(next(ks), d, (d, moe.routed or e), pd)
    if moe.select_bias:
        p["router_bias"] = jnp.zeros((moe.routed or e,), jnp.float32)
    p["w_up"] = _init_linear(next(ks), d, (e, d, f), pd)
    if moe.gated:
        p["w_gate"] = _init_linear(next(ks), d, (e, d, f), pd)
    p["w_down"] = _init_linear(next(ks), f, (e, f, d), pd)
    if moe.shared_d_ff:
        fs = moe.shared_d_ff
        p["ws_up"] = _init_linear(next(ks), d, (d, fs), pd)
        p["ws_gate"] = _init_linear(next(ks), d, (d, fs), pd)
        p["ws_down"] = _init_linear(next(ks), fs, (fs, d), pd)
        if moe.shared_gate:
            p["ws_sg"] = _init_linear(next(ks), d, (d,), pd)
    return p


def _pattern_init(key: jax.Array, cfg: TransformerConfig) -> Dict:
    """A pattern's parameters: ``lead/<i>`` one layer each, ``period/<r>``
    run r of the period with its layers stacked ``[periods, run length,
    ...]`` (string keys, so a leaf has a path), ``embed``, ``ln_f`` and,
    untied, ``head``."""
    d, pd = cfg.d_model, cfg.param_dtype
    k_embed, k_head, k_lead, k_period = jax.random.split(key, 4)
    params = {
        "embed": (jax.random.normal(k_embed, (cfg.vocab, d)) * 0.02
                  ).astype(pd),
        "ln_f": _norm_gain(cfg, (d,)),
        "lead": {str(i): _init_layer(k, cfg, kind) for i, (k, kind) in
                 enumerate(zip(jax.random.split(k_lead,
                                                max(len(cfg.leading), 1)),
                               cfg.leading))},
        "period": {},
    }
    if not cfg.tie_head:
        params["head"] = (jax.random.normal(
            k_head, (cfg.pred_heads * cfg.vocab, d)) * 0.02).astype(pd)
    runs = cfg.period_runs
    for r, (k_run, (kind, count)) in enumerate(
            zip(jax.random.split(k_period, len(runs)), runs)):
        layers = [_init_layer(k, cfg, kind)
                  for k in jax.random.split(k_run, cfg.periods * count)]
        params["period"][str(r)] = jax.tree.map(
            lambda *xs: jnp.stack(xs).reshape(
                (cfg.periods, count) + xs[0].shape), *layers)
    return params


def transformer_init(key: jax.Array, cfg: TransformerConfig) -> Dict:
    """Parameter pytree. Block params are stacked [layers, ...] for scan;
    under pp they are reshaped to [pp, layers_per_stage, ...] at apply time
    (same memory layout, stage-major).  A pattern configuration's tree is
    :func:`_pattern_init`'s."""
    if cfg.period:
        return _pattern_init(key, cfg)
    keys = jax.random.split(key, 8)
    d, h, hk, dh, f = (cfg.d_model, cfg.heads, cfg.kv_heads, cfg.head_dim,
                       cfg.d_ff)
    L = cfg.layers
    pd = cfg.param_dtype

    def stack(initfn, subkey):
        return jnp.stack([initfn(k) for k in jax.random.split(subkey, L)])

    block = {
        "ln1": jnp.ones((L, d), pd),
        "ln2": jnp.ones((L, d), pd),
        "wq": stack(lambda k: _init_linear(k, d, (d, h * dh), pd), keys[1]),
        "wk": stack(lambda k: _init_linear(k, d, (d, hk * dh), pd), keys[2]),
        "wv": stack(lambda k: _init_linear(k, d, (d, hk * dh), pd), keys[3]),
        "wo": stack(lambda k: _init_linear(k, h * dh, (h * dh, d), pd),
                    keys[4]),
    }
    if cfg.num_experts:
        e = cfg.num_experts
        block["w_router"] = stack(
            lambda k: _init_linear(k, d, (d, e), pd), keys[5])
        block["w_up"] = stack(
            lambda k: _init_linear(k, d, (e, d, f), pd), keys[6])
        block["w_down"] = stack(
            lambda k: _init_linear(k, f, (e, f, d), pd), keys[7])
    else:
        block["w_up"] = stack(lambda k: _init_linear(k, d, (d, f), pd),
                              keys[5])
        block["w_gate"] = stack(lambda k: _init_linear(k, d, (d, f), pd),
                                keys[6])
        block["w_down"] = stack(lambda k: _init_linear(k, f, (f, d), pd),
                                keys[7])
    return {
        "embed": (jax.random.normal(keys[0], (cfg.vocab, d)) * 0.02
                  ).astype(pd),
        "ln_f": jnp.ones((d,), pd),
        "block": block,
    }


def transformer_logical_axes(cfg: TransformerConfig) -> Dict:
    """Same-structure pytree of logical axis names (None = replicated dim)
    for ``parallel.sharding.logical_to_mesh``. Leading stacked-layers dim
    maps to "stages" so pp shards it when the mesh has a pp axis.  A
    pattern's stacking dims are replicated (it runs no pipeline island)."""
    if cfg.period:
        return _pattern_logical_axes(cfg)
    block = {
        "ln1": ("stages", None),
        "ln2": ("stages", None),
        "wq": ("stages", "embed", "heads"),
        "wk": ("stages", "embed", "kv"),
        "wv": ("stages", "embed", "kv"),
        "wo": ("stages", "heads", "embed"),
    }
    if cfg.num_experts:
        block["w_router"] = ("stages", "embed", None)
        block["w_up"] = ("stages", "experts", "embed", "mlp")
        block["w_down"] = ("stages", "experts", "mlp", "embed")
    else:
        block["w_up"] = ("stages", "embed", "mlp")
        block["w_gate"] = ("stages", "embed", "mlp")
        block["w_down"] = ("stages", "mlp", "embed")
    return {"embed": ("vocab", "embed"), "ln_f": (None,), "block": block}


def _pattern_logical_axes(cfg: TransformerConfig) -> Dict:
    def layer(kind: LayerKind, stacked: int):
        lead = (None,) * stacked
        axes = {"ln1": (None,), "ln2": (None,)}
        if kind.linear is not None:
            axes.update(w_qkvz=("embed", "heads"), w_ba=("embed", None),
                        conv=(None, "heads"), a_log=(None,),
                        dt_bias=(None,), gdn_norm=(None,),
                        w_out=("heads", "embed"))
        elif kind.ssm is not None:
            # Whole on every tp rank: the gated norm spans the heads.
            axes.update(w_in=("embed", None), conv=(None, None),
                        a_log=(None,), d_skip=(None,), dt_bias=(None,),
                        ssd_norm=(None,), w_out=(None, "embed"))
            if kind.ssm.conv_bias:
                axes["conv_bias"] = (None,)
        elif kind.conv is not None:
            # Whole on every tp rank: [B | C | X] are three blocks of one
            # dimension, which a contiguous shard would cut across.
            axes.update(w_in=("embed", None), conv=(None, None),
                        w_out=(None, "embed"))
            if kind.conv.bias:
                axes["conv_bias"] = (None,)
        else:
            axes.update(wq=("embed", "heads"), wk=("embed", "kv"),
                        wv=("embed", "kv"), wo=("heads", "embed"))
            if cfg.out_gate == "head":
                axes["wg"] = ("embed", "heads")
            if cfg.qk_norm:
                axes.update(q_norm=(None,), k_norm=(None,))
            if kind.eva is not None:
                axes.update(phi=("heads", None), mu=("heads", None))
        if kind.sparse:
            axes.update(w_router=("embed", None),
                        w_up=("experts", "embed", "mlp"),
                        w_down=("experts", "mlp", "embed"))
            if cfg.moe.select_bias:
                axes["router_bias"] = (None,)
            if cfg.moe.gated:
                axes["w_gate"] = ("experts", "embed", "mlp")
            if cfg.moe.shared_d_ff:
                axes.update(ws_up=("embed", "mlp"), ws_gate=("embed", "mlp"),
                            ws_down=("mlp", "embed"))
                if cfg.moe.shared_gate:
                    axes["ws_sg"] = ("embed",)
        else:
            axes.update(w_up=("embed", "mlp"), w_gate=("embed", "mlp"),
                        w_down=("mlp", "embed"))
        return {name: lead + ax for name, ax in axes.items()}

    out = {"embed": ("vocab", "embed"), "ln_f": (None,),
           "lead": {str(i): layer(kind, 0)
                    for i, kind in enumerate(cfg.leading)},
           "period": {str(r): layer(kind, 2)
                      for r, (kind, _) in enumerate(cfg.period_runs)}}
    if not cfg.tie_head:
        out["head"] = ("vocab", "embed")
    return out


def _rmsnorm(x, g, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(
        x.dtype) * g.astype(x.dtype)


def _norm(x, g, cfg: TransformerConfig):
    """The configuration's RMSNorm: scaled by the gain, or by 1 + gain."""
    return _rmsnorm(x, 1.0 + g.astype(jnp.float32)
                    if cfg.zero_centered_norm else g, cfg.norm_eps)


def _rope_frequencies(rope: Rope, head_dim: int) -> np.ndarray:
    """The rotary frequencies of ``rope`` [dim / 2], float32.  Plain:
    theta^(-2i/dim).  YaRN, as ``transformers`` computes it: interpolated
    (/ factor) below rotation count ``beta_slow`` over the original
    context, extrapolated (unchanged) above ``beta_fast``, a linear ramp
    over the dimensions between."""
    dim = rope.dim or head_dim
    i = np.arange(dim // 2, dtype=np.float64)
    extrapolated = rope.theta ** (-2.0 * i / dim)
    if not rope.yarn_factor:
        return extrapolated.astype(np.float32)

    def correction(rotations):
        return (dim * math.log(rope.yarn_original_max
                               / (rotations * 2 * math.pi))
                / (2 * math.log(rope.theta)))

    low = max(math.floor(correction(rope.yarn_beta_fast)), 0)
    high = min(math.ceil(correction(rope.yarn_beta_slow)), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (extrapolated / rope.yarn_factor * ramp
            + extrapolated * (1.0 - ramp)).astype(np.float32)


def _rope_tables(positions, rope: Rope, head_dim: int):
    """One head's tables under ``rope``'s settings at ``positions`` [B, L]
    (global token positions), as ``ops.pallas_kernels.rope`` takes them:
    (cos, sin) float32 [B, L, head_dim], and the distance between the
    lanes of a pair.  The first ``rope.dim`` dimensions of a head rotate
    (rotate-half inside them: both lanes of a pair take the pair's cos,
    and its sin with a minus on the lower one), the rest pass through
    (1 and 0); cos and sin times ``attention_factor``."""
    dim = rope.dim or head_dim
    ang = (positions[..., None].astype(jnp.float32)
           * jnp.asarray(_rope_frequencies(rope, head_dim)))  # [B, L, dim/2]
    cos = jnp.cos(ang) * rope.attention_factor
    sin = jnp.sin(ang) * rope.attention_factor
    rest = jnp.zeros(ang.shape[:-1] + (head_dim - dim,), jnp.float32)
    return (jnp.concatenate([cos, cos, rest + 1.0], -1),
            jnp.concatenate([-sin, sin, rest], -1), dim // 2)


def _proj(x, w):
    """Dense projection ``x @ w`` in the activation dtype — rides the
    per-tensor-scaled fp8 (e4m3) convert-dot when ``HVDT_FP8=matmul``
    and the backend supports it (quant/fp8.py); otherwise exactly the
    plain matmul.  The gate is resolved at trace time from env config,
    so flipping HVDT_FP8 recompiles rather than branching in-graph."""
    if _fp8.matmul_enabled():
        return _fp8.fp8_matmul(x, w)
    return x @ w.astype(x.dtype)


def _qkv_gate(p, x, positions, cfg: TransformerConfig,
              kind: Optional[LayerKind] = None):
    """Rotated q/k/v projections — the one place the projection + RoPE
    recipe lives, shared by training attention (:func:`_attention`) and
    the serving paged-KV prefill/decode paths, so the cache can never
    hold keys rotated differently from the ones training computed —
    and, where the output gate is the query projection's second half, the
    gate's pre-activation [B, L, H, D] (else None).  ``kind`` is the
    layer's (None: the uniform configuration's)."""
    kind = kind or cfg.uniform_kind
    b, l, _ = x.shape
    h, hk, dh = kind.heads, kind.kv_heads, cfg.head_dim
    with jax.named_scope("hvdt.attention.qkv"):
        q, gate = _proj(x, p["wq"]), None
        if cfg.out_gate == "elementwise":
            q, gate = jnp.split(q.reshape(b, l, h, 2 * dh), 2, axis=-1)
        k = _proj(x, p["wk"])
        v = _proj(x, p["wv"]).reshape(b, l, hk, dh)
    with jax.named_scope("hvdt.attention.rope"):
        # RoPE on the projections' own rows [B, L, H * D], or on the heads
        # a q / k norm leaves: ``rope`` reads which from the shape.
        if cfg.qk_norm:
            q = _norm(q.reshape(b, l, h, dh), p["q_norm"], cfg)
            k = _norm(k.reshape(b, l, hk, dh), p["k_norm"], cfg)
        if kind.rope is None:           # no position term
            q, k = q.reshape(b, l, h, dh), k.reshape(b, l, hk, dh)
        else:
            tables = _rope_tables(positions, kind.rope, dh)
            q = rope(q, *tables).reshape(b, l, h, dh)
            k = rope(k, *tables).reshape(b, l, hk, dh)
    return q, k, v, gate


def _qkv(p, x, positions, cfg: TransformerConfig,
         kind: Optional[LayerKind] = None):
    """:func:`_qkv_gate`'s q, k, v (the serving paths': no gate there)."""
    return _qkv_gate(p, x, positions, cfg, kind)[:3]


def _attention(p, x, positions, cfg: TransformerConfig,
               kind: Optional[LayerKind] = None):
    kind = kind or cfg.uniform_kind
    b, l, _ = x.shape
    q, k, v, gate = _qkv_gate(p, x, positions, cfg, kind)
    # The kernels and all that ops/attention.py puts around them.
    with jax.named_scope("hvdt.attention.core"):
        if cfg.sp > 1:
            # Manual island: the sequence dim is the local sp shard here
            # (the caller's shard_map over {'sp'} has already split it).
            o = ring_attention(q, k, v, axis="sp", causal=True)
        elif kind.eva is not None:
            o = eva_attention(q, k, v, p["phi"], p["mu"],
                              window=kind.eva.window, chunk=kind.eva.chunk)
        else:
            o = attention(q, k, v, window=kind.window,
                          block_diffusion=cfg.diffusion_block or None,
                          scale=cfg.attention_multiplier or None)
    with jax.named_scope("hvdt.attention.gate"):
        if cfg.out_gate == "head":
            # A gate a head, from the layer's normed input.
            gate = jax.nn.sigmoid(_proj(x, p["wg"]).astype(jnp.float32))
            o = o * gate.astype(o.dtype)[..., None]
        elif gate is not None:
            o = o * jax.nn.sigmoid(
                gate.astype(jnp.float32)).astype(o.dtype)
    with jax.named_scope("hvdt.attention.out"):
        return _proj(o.reshape(b, l, kind.heads * cfg.head_dim), p["wo"])


def _mlp(p, x):
    up = _proj(x, p["w_up"])
    gate = jax.nn.silu(_proj(x, p["w_gate"]))
    return _proj(up * gate, p["w_down"])


def _moe_mlp(p, x, cfg: TransformerConfig):
    """The sparse feed-forward.  On one device (``ep == 1``) the dropless
    layer over the experts held here (``parallel.moe.moe_held_experts``,
    under ``hvdt.moe``); across an ``ep`` axis the capacity dispatcher."""
    b, l, d = x.shape
    tokens = x.reshape(b * l, d)
    if cfg.ep > 1:
        logits = tokens @ p["w_router"].astype(x.dtype)
        w_up, w_down = (p["w_up"].astype(x.dtype),
                        p["w_down"].astype(x.dtype))

        # w_up/w_down enter the island sharded over ep on the expert dim.
        def expert_fn(toks):   # [E_local, N, D]
            hmid = jax.nn.silu(jnp.einsum("end,edf->enf", toks, w_up))
            return jnp.einsum("enf,efd->end", hmid, w_down)
        out, aux = moe_dispatch_combine(
            tokens, logits, expert_fn, axis="ep",
            experts_per_rank=cfg.num_experts // cfg.ep,
            capacity_factor=cfg.capacity_factor)
        return out.reshape(b, l, d), aux
    moe = cfg.experts
    shared = None
    if moe.shared_d_ff:
        def shared(h):
            y = _mlp({"w_up": p["ws_up"], "w_gate": p["ws_gate"],
                      "w_down": p["ws_down"]}, h)
            if moe.shared_gate:
                y = y * jax.nn.sigmoid(
                    h.astype(jnp.float32) @ p["ws_sg"].astype(jnp.float32)
                )[:, None].astype(y.dtype)
            return y
    with jax.named_scope("hvdt.moe"):
        out, aux = moe_held_experts(
            tokens, p["w_router"], p["w_up"], p["w_down"], p.get("w_gate"),
            top_k=moe.per_token, experts_first=moe.first, score=moe.score,
            normalize=moe.normalize, scale=moe.scale,
            select_bias=p["router_bias"] if moe.select_bias else None,
            normalize_eps=moe.normalize_eps, shared_fn=shared)
    return out.reshape(b, l, d), aux


def _dots_policy():
    """The ``dots_with_no_batch_dims_saveable`` checkpoint policy, or
    ``None`` on jax builds that don't ship it (the container's 0.4.37
    has it, but the guard keeps HVDT_REMAT=dots from crashing older/
    newer builds that rename it)."""
    policies = getattr(jax, "checkpoint_policies", None)
    return getattr(policies, "dots_with_no_batch_dims_saveable", None)


_REMAT_MODES = ("none", "full", "dots")


def checkpoint_policy(mode: Optional[str] = None):
    """Resolve an ``HVDT_REMAT`` mode to a ``jax.checkpoint`` wrapper
    argument: ``None`` (no remat), the string sentinel ``"full"`` (plain
    ``jax.checkpoint``), or a policy callable (``dots``).  ``mode=None``
    reads the env knob; unknown modes raise with the valid list; a
    ``dots`` request on a build without the policy degrades to ``full``
    with a warning (never a crash)."""
    from ..common import config
    from ..common.logging_util import get_logger

    if mode is None:
        mode = config.get_str("HVDT_REMAT")
    mode = (mode or "none").strip().lower() or "none"
    if mode not in _REMAT_MODES:
        raise ValueError(
            f"unknown HVDT_REMAT mode {mode!r}; valid: "
            f"{', '.join(_REMAT_MODES)}")
    if mode == "none":
        return None
    if mode == "dots":
        pol = _dots_policy()
        if pol is None:
            get_logger(__name__).warning(
                "HVDT_REMAT=dots requested but this jax build has no "
                "dots_with_no_batch_dims_saveable policy; falling back "
                "to remat='full'")
            return "full"
        return pol
    return "full"


def remat_from_env(cfg: TransformerConfig,
                   mode: Optional[str] = None) -> TransformerConfig:
    """Apply the ``HVDT_REMAT`` knob (``none|full|dots``) to a config —
    the memory-for-MFU trade surfaced as
    ``hvdtrun --remat``.  Returns ``cfg`` unchanged for ``none`` (and
    the ``dots``→``full`` fallback is resolved here so the config names
    the policy that will actually run)."""
    pol = checkpoint_policy(mode)
    if pol is None:
        return dataclasses.replace(cfg, remat=False)
    policy_name = "full" if pol == "full" else "dots"
    return dataclasses.replace(cfg, remat=True, remat_policy=policy_name)


def _residual(x, y, cfg: TransformerConfig):
    """The residual stream plus a sublayer's output, times the
    configuration's ``residual_multiplier``."""
    if cfg.residual_multiplier != 1.0:
        y = y * cfg.residual_multiplier
    return x + y


def _block(p, x, positions, cfg: TransformerConfig,
           kind: Optional[LayerKind] = None):
    """One layer of ``kind`` (None: the uniform configuration's)."""
    kind = kind or cfg.uniform_kind
    # Each sublayer with its pre-norm under one name, for the profiler
    # and the benchmark's phase split (docs/observability.md).
    if kind.linear is not None:
        # A sibling of hvdt.attention, so that attention_ms keeps meaning
        # softmax attention.
        with jax.named_scope("hvdt.gdn"):
            a = gated_delta_net(_norm(x, p["ln1"], cfg), p, proj=_proj,
                                **kind.linear.sizes)
    elif kind.ssm is not None:
        with jax.named_scope("hvdt.ssd"):
            a = mamba2_mixer(_norm(x, p["ln1"], cfg), p, proj=_proj,
                             eps=cfg.norm_eps, **kind.ssm.sizes)
    elif kind.conv is not None:
        with jax.named_scope("hvdt.sconv"):
            a = gated_short_conv(_norm(x, p["ln1"], cfg), p, proj=_proj)
    else:
        with jax.named_scope("hvdt.attention"):
            a = _attention(p, _norm(x, p["ln1"], cfg), positions, cfg, kind)
    x = _residual(x, a, cfg)
    with jax.named_scope("hvdt.mlp"):
        if kind.sparse:
            y, _ = _moe_mlp(p, _norm(x, p["ln2"], cfg), cfg)
        else:
            y = _mlp(p, _norm(x, p["ln2"], cfg))
    return _residual(x, y, cfg)


def _layer_fn(positions, cfg: TransformerConfig,
              kind: Optional[LayerKind] = None):
    """``(layer params, x) -> x`` of one layer of ``kind`` under the
    configuration's rematerialization policy."""
    body = functools.partial(_block, positions=positions, cfg=cfg, kind=kind)
    if cfg.remat:
        # Either policy also keeps the expert layer's route
        # (TransformerConfig.remat_policy); nothing more without one.
        policies = jax.checkpoint_policies
        keep = policies.save_only_these_names(ROUTE_SAVED)
        if cfg.remat_policy == "dots":
            pol = _dots_policy()
            if pol is None:
                # Guarded for jax builds without the named policy
                # (HVDT_REMAT=dots on such a build degrades to 'full'
                # at config time; a hand-built config degrades here).
                from ..common.logging_util import get_logger

                get_logger(__name__).warning(
                    "remat_policy='dots' unavailable on this jax "
                    "build; using 'full'")
            else:
                keep = policies.save_from_both_policies(pol, keep)
        elif cfg.remat_policy != "full":
            raise ValueError(
                f"unknown remat_policy {cfg.remat_policy!r} "
                "(expected 'full' or 'dots')")
        body = jax.checkpoint(body, policy=keep)
    return body


def _scan_blocks(block_params, x, positions, cfg: TransformerConfig,
                 kind: Optional[LayerKind] = None):
    body = _layer_fn(positions, cfg, kind)

    def step(h, layer_p):
        return body(layer_p, h), None

    out, _ = lax.scan(step, x, block_params)
    return out


def _pattern_blocks(params, x, positions, cfg: TransformerConfig):
    """The layers of a pattern: the leading ones one by one, then a scan
    over the periods whose body scans each run of equal neighbours."""
    for i, kind in enumerate(cfg.leading):
        x = _layer_fn(positions, cfg, kind)(params["lead"][str(i)], x)

    def one_period(h, period_p):
        for r, (kind, _) in enumerate(cfg.period_runs):
            h = _scan_blocks(period_p[str(r)], h, positions, cfg, kind)
        return h, None

    x, _ = lax.scan(one_period, x, params["period"])
    return x


def transformer_hidden(params: Dict, tokens: jax.Array,
                       cfg: TransformerConfig) -> jax.Array:
    """Final-norm hidden states [batch, seq, d_model] (everything but the
    vocab projection — split out so the chunked loss can avoid ever
    materializing [batch, seq, vocab] logits).

    tokens: [batch, seq] int32 — the *local* sp shard of the sequence when
    called inside a shard_map over {'sp'} (positions are globalized with
    the sp rank), the full sequence otherwise.  Under
    ``cfg.diffusion_block`` the rows are the two streams [noisy ; clean]
    of seq / 2 tokens, each at positions 0 .. seq / 2 - 1.
    """
    b, l = tokens.shape
    if cfg.sp > 1:
        offset = lax.axis_index("sp") * l
    else:
        offset = 0
    positions = offset + jnp.broadcast_to(jnp.arange(l), (b, l))
    if cfg.diffusion_block:
        # RoPE sees a token's place in its sequence, not its row.
        positions = positions % (l // 2)
    with jax.named_scope("hvdt.embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
    # Manual-island axes make activations varying (e.g. the MoE alltoall);
    # pre-cast so the scan-over-layers carry is type-stable under vma.
    from ..parallel.sharding import pcast_to_union

    manual_axes = [ax for ax, on in (("sp", cfg.sp > 1),
                                     ("ep", cfg.ep > 1 and cfg.num_experts))
                   if on]
    x = pcast_to_union(x, extra=tuple(manual_axes))
    if cfg.period:
        x = pcast_to_union(x, *jax.tree.leaves(
            (params["lead"], params["period"])))
        x = _pattern_blocks(params, x, positions, cfg)
    elif cfg.pp > 1:
        # Inside a shard_map over {'pp'} the stacked-layers dim of the
        # block params is the sharded "stages" logical axis, so the local
        # slice is already this rank's [layers_per_stage, ...] stage.
        # Microbatch over batch dim with M = pp (minimum schedule).
        m = cfg.pp
        assert b % m == 0, f"batch {b} not divisible by pp {cfg.pp}"
        mb = b // m
        acts = x.reshape(m, mb, l, cfg.d_model)
        pos_mb = positions.reshape(m, mb, l)

        def stage_fn(stage_p, a):
            # positions are identical across microbatches in this layout
            return _scan_blocks(stage_p, a, pos_mb[0], cfg)

        x = pipeline_1f1b(stage_fn, params["block"], acts, axis="pp")
        x = x.reshape(b, l, cfg.d_model)
    else:
        # Block params may still be varying on manual axes the config
        # doesn't know about (e.g. a stages dim spec'd onto a size-1 pp
        # mesh axis); the scan carry must match, so pcast x up to the
        # union of the params' varying axes.
        from ..parallel.sharding import pcast_to_union

        x = pcast_to_union(x, *jax.tree.leaves(params["block"]))
        x = _scan_blocks(params["block"], x, positions, cfg)
    return _norm(x, params["ln_f"], cfg)


def transformer_apply(params: Dict, tokens: jax.Array,
                      cfg: TransformerConfig) -> jax.Array:
    """Logits for next-token prediction (see transformer_hidden); with
    ``cfg.pred_heads`` n > 1, [batch, seq, n x vocab]: columns m vocab ..
    (m + 1) vocab - 1 of row i are head m's, for token i + 1 + m."""
    return _head(params, transformer_hidden(params, tokens, cfg), cfg)


def _head_matrix(params: Dict, cfg: TransformerConfig) -> jax.Array:
    """The output projection's [vocab, d_model] matrix: the embedding
    where the head is tied, else the tree's own ``head``, [pred_heads x
    vocab, d_model] (head m's rows after head m - 1's).  Over a slice of
    the vocabulary both have the slice's rows, and logits and loss are
    over the slice."""
    return params["embed"] if cfg.tie_head else params["head"]


def _head(params: Dict, x: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """The output projection: hidden states to f32 logits, over the
    configuration's ``logits_scaling``."""
    logits = (x @ _head_matrix(params, cfg).astype(x.dtype).T
              ).astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def _chunked_xent(x: jax.Array, embed: jax.Array, targets: jax.Array,
                  chunk: int, weights: Optional[jax.Array] = None,
                  divisor: float = 1.0) -> jax.Array:
    """Cross entropy without the [tokens, vocab] logits: scan over vocab
    chunks with an online logsumexp, checkpointed so the backward pass
    recomputes each chunk's logits instead of saving them.  Peak memory
    per step drops from O(tokens x vocab) f32 to O(tokens x chunk) —
    the lever that lets BERT-Large-scale batches fit in HBM (measured:
    dense f32 logits at batch 128 x seq 512 x 30k vocab are 8 GB alone).
    Numerics match the dense path up to fp reassociation.  The mean over
    the tokens, or with ``weights`` [b, t] the sum of each token's term
    times its weight over their number.  The logits are divided by
    ``divisor`` (a configuration's ``logits_scaling``)."""
    b, t, d = x.shape
    vocab = embed.shape[0]
    n_chunks = -(-vocab // chunk)
    pad = n_chunks * chunk - vocab
    w = embed.astype(x.dtype)
    if pad:
        w = jnp.concatenate([w, jnp.zeros((pad, d), x.dtype)])
    w = w.reshape(n_chunks, chunk, d)
    xf = x.reshape(b * t, d)
    tgt = targets.reshape(b * t)

    def body(carry, wc_ci):
        m, s, tl = carry
        wc, ci = wc_ci
        logits = (xf @ wc.T).astype(jnp.float32)        # [N, chunk]
        if divisor != 1.0:
            logits = logits / divisor
        base = ci * chunk
        valid = (jnp.arange(chunk) + base) < vocab
        logits = jnp.where(valid[None, :], logits, -jnp.inf)
        m_new = jnp.maximum(m, logits.max(-1))
        s = (s * jnp.exp(m - m_new)
             + jnp.exp(logits - m_new[:, None]).sum(-1))
        in_chunk = (tgt >= base) & (tgt < base + chunk)
        idx = jnp.clip(tgt - base, 0, chunk - 1)
        tl = jnp.where(
            in_chunk,
            jnp.take_along_axis(logits, idx[:, None], 1)[:, 0], tl)
        return (m_new, s, tl), None

    init = (jnp.full((b * t,), -jnp.inf, jnp.float32),
            jnp.zeros((b * t,), jnp.float32),
            jnp.zeros((b * t,), jnp.float32))
    # Inside a shard_map island (sp/pp) the hidden states are varying, so
    # the scan body's outputs are too — the carry init must match the
    # body's output vma or the scan type check rejects it.
    vma = tuple(set(jax.typeof(xf).vma) | set(jax.typeof(tgt).vma))
    if vma:
        init = jax.tree.map(lambda a: lax.pcast(a, vma, to="varying"), init)
    (m, s, tl), _ = lax.scan(jax.checkpoint(body), init,
                             (w, jnp.arange(n_chunks)))
    if weights is not None:
        return ((jnp.log(s) + m - tl) * weights.reshape(b * t)).mean()
    return (jnp.log(s) + m - tl).mean()


def _multi_target_xent(x: jax.Array, head: jax.Array, tokens: jax.Array,
                       heads: int, chunk: int,
                       divisor: float = 1.0) -> jax.Array:
    """The loss of ``heads`` prediction heads a row: x [b, l, d] the final
    hidden rows, head [heads x vocab, d], tokens [b, l].  Head m of row i
    is scored against token i + 1 + m; the mean of -log softmax over every
    (row, head) pair that has a target, the heads weighted alike.  Rows in
    chunks of ``chunk`` (0: all at once) under a checkpoint, so that only
    a chunk's float32 logits [chunk, heads x vocab] exist at a time, in
    the forward and in the backward."""
    b, l, d = x.shape
    vocab = head.shape[0] // heads
    ahead = jnp.pad(tokens, ((0, 0), (0, heads)))
    targets = jnp.stack([ahead[:, 1 + m:1 + m + l] for m in range(heads)],
                        -1)                                 # [b, l, heads]
    valid = jnp.broadcast_to(
        jnp.arange(l)[:, None] + jnp.arange(heads) + 1 < l, (b, l, heads))
    rows = b * l
    size = next(c for c in range(min(chunk or rows, rows), 0, -1)
                if rows % c == 0)
    w = head.astype(x.dtype)

    def body(total, chunk_of):
        xc, tc, vc = chunk_of
        logits = (xc @ w.T).astype(jnp.float32).reshape(size, heads, vocab)
        if divisor != 1.0:
            logits = logits / divisor
        logp = jax.nn.log_softmax(logits, -1)
        ll = jnp.take_along_axis(logp, tc[..., None], -1)[..., 0]
        return total - jnp.where(vc, ll, 0.0).sum(), None

    total = jnp.zeros((), jnp.float32)
    # As _chunked_xent: the carry takes the body's varying axes.
    vma = tuple(set(jax.typeof(x).vma) | set(jax.typeof(tokens).vma))
    if vma:
        total = lax.pcast(total, vma, to="varying")
    total, _ = lax.scan(
        jax.checkpoint(body), total,
        (x.reshape(-1, size, d), targets.reshape(-1, size, heads),
         valid.reshape(-1, size, heads)))
    return total / (b * sum(max(l - 1 - m, 0) for m in range(heads)))


def transformer_loss(params: Dict, tokens: jax.Array,
                     cfg: TransformerConfig) -> jax.Array:
    """Causal LM loss (next-token cross entropy) over the local shard.

    The model runs on the FULL sequence and the last position's
    prediction is dropped — mathematically identical to feeding
    ``tokens[:, :-1]`` (causal attention means position i never sees
    i+1), but it keeps the attention length at the caller's power-of-two
    ``seq`` instead of ``seq - 1``, which is what lets the flash kernel
    (block-divisibility gate) engage on the training path.

    ``cfg.loss_chunk > 0`` switches to the chunked-vocab logsumexp path
    (no [tokens, vocab] logits tensor).  ``cfg.pred_heads`` n > 1: n
    targets a row, head m of row i against token i + 1 + m
    (:func:`_multi_target_xent`, chunked over rows)."""
    targets = tokens[:, 1:]
    x = transformer_hidden(params, tokens, cfg)
    # The head's matmul is inside the scope on both branches.
    with jax.named_scope("hvdt.loss"):
        if cfg.pred_heads > 1:
            return _multi_target_xent(x, _head_matrix(params, cfg), tokens,
                                      cfg.pred_heads, cfg.loss_chunk,
                                      cfg.logits_scaling)
        if cfg.loss_chunk:
            return _chunked_xent(x[:, :-1], _head_matrix(params, cfg),
                                 targets, cfg.loss_chunk,
                                 divisor=cfg.logits_scaling)
        logits = _head(params, x, cfg)[:, :-1]
        logp = jax.nn.log_softmax(logits, -1)
        ll = jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
        return -ll.mean()


def block_diffusion_corrupt(key: jax.Array, tokens: jax.Array, *,
                            block: int, mask_id: int, eps: float = 1e-3):
    """The forward process of diffusion over blocks on ``tokens`` [batch,
    L]: (x_t, t, masked).  Each block of ``block`` tokens gets its own
    noise level t in [eps, 1) (the linear schedule: a token survives with
    probability 1 - t) and each of its tokens is replaced by ``mask_id``
    independently with probability t.  t is stratified over the blocks of
    a sequence: t_b = eps + (1 - eps) ((u + b / n) mod 1) with one u a
    sequence, so every sequence holds every noise level once and each
    block's t is still uniform.  A block's loss term reads its own t and
    the clean tokens before it alone, so how the blocks' t are coupled
    moves no expectation.  t: float32 [batch, L / block]; masked: bool
    [batch, L]."""
    b, l = tokens.shape
    n = l // block
    if l != n * block:
        raise ValueError(f"{l} tokens are not whole blocks of {block}")
    k_t, k_mask = jax.random.split(key)
    u = jax.random.uniform(k_t, (b, 1))
    t = eps + (1.0 - eps) * ((u + jnp.arange(n) / n) % 1.0)
    masked = jax.random.uniform(k_mask, (b, l)) < jnp.repeat(t, block, 1)
    return jnp.where(masked, mask_id, tokens), t, masked


def transformer_block_diffusion_loss(params: Dict, tokens: jax.Array,
                                     t: jax.Array, masked: jax.Array,
                                     cfg: TransformerConfig) -> jax.Array:
    """The loss of diffusion over blocks of ``cfg.diffusion_block`` tokens
    (BD3-LM, arXiv:2503.09573, as SDAR, arXiv:2510.06303, trains) on
    ``tokens`` [batch, L] corrupted as ``block_diffusion_corrupt`` draws
    it (``t`` [batch, L / block], ``masked`` [batch, L]; the mask token is
    the last held row of the vocabulary, ``cfg.vocab - 1``):

        (1 / (batch L)) sum_b (1 / t_b) sum_{i in b, masked}
            -log softmax(head(h_i))[tokens_i]

    ONE pass over the 2 L rows [x_t ; tokens]: under the block mask row i
    of the noisy half is what a pass over [tokens of the blocks before
    its own ; x_t of its own block] would give, for every block at once.
    The loss reads the noisy half's rows, each at its own position (no
    shift by one: a masked row predicts its own token)."""
    l = tokens.shape[1]
    block = cfg.diffusion_block
    with jax.named_scope("hvdt.embed"):
        rows = jnp.concatenate(
            [jnp.where(masked, cfg.vocab - 1, tokens), tokens], axis=1)
    x = transformer_hidden(params, rows, cfg)
    with jax.named_scope("hvdt.loss"):
        x = x[:, :l]
        weights = masked / jnp.repeat(t, block, axis=1)
        if cfg.loss_chunk:
            return _chunked_xent(x, _head_matrix(params, cfg), tokens,
                                 cfg.loss_chunk, weights,
                                 cfg.logits_scaling)
        logp = jax.nn.log_softmax(_head(params, x, cfg), -1)
        ll = jnp.take_along_axis(logp, tokens[..., None], -1)[..., 0]
        return -(ll * weights).mean()


# ---------------------------------------------------------------------------
# Paged-KV serving paths (serve/llm continuous-batching engine).
#
# The cache layout is ``[layers, num_blocks, block_size, kv_heads,
# head_dim]`` — fixed-size physical blocks indexed per sequence through a
# block table (``serve/llm/kv_cache.py`` owns allocation; this module
# owns the math).  All three entry points have FIXED shapes in every
# argument, so admission/eviction of sequences between iterations can
# never change a jitted program: that is the zero-steady-state-recompile
# contract the static bucket engine pioneered, carried into decode.
#
# Physical block 0 is the write SINK: inactive decode slots and padded
# prefill positions scatter their k/v there, where no block table ever
# points (the allocator never hands block 0 out), so masked lanes stay
# harmless without a single dynamic shape.
# ---------------------------------------------------------------------------


def _uniform_only(cfg: TransformerConfig, what: str) -> None:
    """The paged serving functions scan ``params["block"]`` with one kind
    of layer and one cache shape: a layer pattern (kinds with their own
    heads, windows, experts) is refused here until serving learns it."""
    if cfg.period or cfg.out_gate or not cfg.tie_head or (
            cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) != (1, 1, 0, 1):
        raise NotImplementedError(
            f"{what} takes uniform configurations only: serving a "
            "layer-pattern model (per-kind heads and windows, an output "
            "gate, an untied head, a state-space mixer, multipliers of its "
            "own) is not built yet; train it with transformer_loss")


def _masked_softmax_attn(q, keys, vals, mask):
    """Attention with an explicit mask and a clamped denominator.

    q: [B, Lq, H, D]; keys/vals: [B, T, Hkv, D]; mask: [B, Lq, T] bool.
    Fully-masked rows (inactive decode slots, padded prefill lanes)
    return exactly 0 instead of NaN — ``jax.nn.softmax`` over an
    all-masked row is 0/0, and one NaN hidden row would poison every
    *valid* row at the next layer through its scattered k/v."""
    h, hkv = q.shape[2], keys.shape[2]
    if h != hkv:
        keys = jnp.repeat(keys, h // hkv, axis=2)
        vals = jnp.repeat(vals, h // hkv, axis=2)
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, keys,
                   preferred_element_type=jnp.float32) * scale
    m = mask[:, None]                                   # [B, 1, Lq, T]
    s = jnp.where(m, s, -1e30)
    smax = jax.lax.stop_gradient(s.max(axis=-1, keepdims=True))
    p = jnp.where(m, jnp.exp(s - smax), 0.0)
    denom = jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-9)
    w = (p / denom).astype(vals.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, vals)


def transformer_decode_paged(params, tokens, block_tables, seq_lens,
                             kc, vc, cfg: TransformerConfig,
                             block_size: int):
    """One continuous-batching decode iteration over the paged cache.

    tokens [S] int32 (each slot's current last token), block_tables
    [S, maxb] int32 physical block ids, seq_lens [S] int32 (tokens in
    the sequence INCLUDING the one decoded now; 0 = inactive slot),
    kc/vc [L, num_blocks, block_size, kv_heads, head_dim].

    Per layer: scatter this token's k/v at position ``seq_len - 1``
    (inactive slots scatter into sink block 0), gather the whole block
    table, attend over key positions ``< seq_len``.  Returns
    ``(next_tokens [S] int32, kc, vc)`` — greedy argmax stays in-graph
    so the host transfer per iteration is S ints, not S×vocab logits.
    """
    _uniform_only(cfg, "transformer_decode_paged")
    s_slots = tokens.shape[0]
    maxb = block_tables.shape[1]
    active = seq_lens > 0
    pos = jnp.maximum(seq_lens - 1, 0)                         # [S]
    x = params["embed"].astype(cfg.dtype)[tokens][:, None, :]  # [S,1,d]
    slot_idx = jnp.arange(s_slots)
    key_pos = jnp.arange(maxb * block_size)
    attn_mask = key_pos[None, :] < seq_lens[:, None]           # [S, T]

    def body(h_carry, layer):
        p, kc_l, vc_l = layer
        hx = _rmsnorm(h_carry, p["ln1"])
        q, k, v = _qkv(p, hx, pos[:, None], cfg)
        blk = jnp.where(active,
                        block_tables[slot_idx, pos // block_size], 0)
        off = pos % block_size
        kc_l = kc_l.at[blk, off].set(k[:, 0].astype(kc_l.dtype))
        vc_l = vc_l.at[blk, off].set(v[:, 0].astype(vc_l.dtype))
        keys = kc_l[block_tables].reshape(
            s_slots, maxb * block_size, *kc_l.shape[2:])
        vals = vc_l[block_tables].reshape(
            s_slots, maxb * block_size, *vc_l.shape[2:])
        o = _masked_softmax_attn(q, keys.astype(cfg.dtype),
                                 vals.astype(cfg.dtype),
                                 attn_mask[:, None, :])
        h_carry = h_carry + _proj(
            o.reshape(s_slots, 1, -1), p["wo"])
        h_carry = h_carry + _mlp(p, _rmsnorm(h_carry, p["ln2"]))
        return h_carry, (kc_l, vc_l)

    x, (kc, vc) = lax.scan(body, x, (params["block"], kc, vc))
    x = _rmsnorm(x, params["ln_f"])
    logits = (x[:, 0] @ params["embed"].astype(x.dtype).T
              ).astype(jnp.float32)
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return nxt, kc, vc


def transformer_prefill_paged(params, tokens, ctx_start, n_valid,
                              block_table, kc, vc,
                              cfg: TransformerConfig, block_size: int):
    """One prefill CHUNK of one sequence into the paged cache.

    tokens [C] int32 (zero-padded past ``n_valid``), ctx_start scalar
    int32 (global position of tokens[0]), n_valid scalar int32,
    block_table [maxb] int32.  Scatters the chunk's k/v at its global
    positions (padded lanes go to sink block 0), then attends each chunk
    query over the WHOLE table — chunk i sees chunks 0..i-1 from the
    cache plus its own just-scattered keys, which is what lets a long
    prompt stream through in fixed-shape chunks without ever stalling
    decode for more than one chunk.  Returns ``(kc, vc)``; the last
    prompt token is deliberately NOT prefilled — it enters through the
    decode step, which produces the first generated token.
    """
    _uniform_only(cfg, "transformer_prefill_paged")
    c = tokens.shape[0]
    maxb = block_table.shape[0]
    pos = ctx_start + jnp.arange(c)                            # [C]
    valid = jnp.arange(c) < n_valid
    x = params["embed"].astype(cfg.dtype)[tokens][None]        # [1,C,d]
    key_pos = jnp.arange(maxb * block_size)
    # Causal by global position, bounded by what exists after this
    # chunk scatters; padded queries are fully masked.
    attn_mask = ((key_pos[None, :] <= pos[:, None])
                 & (key_pos[None, :] < ctx_start + n_valid)
                 & valid[:, None])[None]                       # [1,C,T]

    def body(h_carry, layer):
        p, kc_l, vc_l = layer
        hx = _rmsnorm(h_carry, p["ln1"])
        q, k, v = _qkv(p, hx, pos[None], cfg)
        blk = jnp.where(valid, block_table[pos // block_size], 0)
        off = pos % block_size
        kc_l = kc_l.at[blk, off].set(k[0].astype(kc_l.dtype))
        vc_l = vc_l.at[blk, off].set(v[0].astype(vc_l.dtype))
        keys = kc_l[block_table].reshape(
            1, maxb * block_size, *kc_l.shape[2:])
        vals = vc_l[block_table].reshape(
            1, maxb * block_size, *vc_l.shape[2:])
        o = _masked_softmax_attn(q, keys.astype(cfg.dtype),
                                 vals.astype(cfg.dtype), attn_mask)
        h_carry = h_carry + _proj(o.reshape(1, c, -1), p["wo"])
        h_carry = h_carry + _mlp(p, _rmsnorm(h_carry, p["ln2"]))
        return h_carry, (kc_l, vc_l)

    _, (kc, vc) = lax.scan(body, x, (params["block"], kc, vc))
    return kc, vc


def transformer_prefill_collect(params, tokens, cfg: TransformerConfig):
    """Whole-prompt prefill that RETURNS every layer's rotated k/v.

    The long-context prefill path: called inside a ``shard_map`` over
    the ``sp`` axis when ``cfg.sp > 1``, so attention runs as the exact
    :func:`~horovod_tpu.parallel.ring_attention.ring_attention` ring
    while each shard emits its local k/v slab; the caller's out_specs
    reassemble ``[L, B, S, kv_heads, head_dim]`` slabs that the serving
    engine scatters into the paged cache in one shot.  tokens
    [B, S_local] int32.  Returns ``(k_all, v_all)``.
    """
    _uniform_only(cfg, "transformer_prefill_collect")
    b, l = tokens.shape
    if cfg.sp > 1:
        offset = lax.axis_index("sp") * l
    else:
        offset = 0
    positions = offset + jnp.broadcast_to(jnp.arange(l), (b, l))
    x = params["embed"].astype(cfg.dtype)[tokens]
    if cfg.sp > 1:
        # Ring transfers make activations varying on sp; the scan carry
        # must be type-stable under vma (transformer_hidden idiom).
        from ..parallel.sharding import pcast_to_union

        x = pcast_to_union(x, extra=("sp",))

    def body(h_carry, p):
        hx = _rmsnorm(h_carry, p["ln1"])
        q, k, v = _qkv(p, hx, positions, cfg)
        if cfg.sp > 1:
            o = ring_attention(q, k, v, axis="sp", causal=True)
        else:
            mask = jnp.tril(jnp.ones((l, l), bool))[None]
            o = _masked_softmax_attn(q, k, v, mask)
        h_carry = h_carry + _proj(o.reshape(b, l, -1), p["wo"])
        h_carry = h_carry + _mlp(p, _rmsnorm(h_carry, p["ln2"]))
        return h_carry, (k, v)

    _, (k_all, v_all) = lax.scan(body, x, params["block"])
    return k_all, v_all


def transformer_flops_per_token(cfg: TransformerConfig) -> float:
    """Approximate forward-pass matmul FLOPs per token (for MFU metrics):
    the full score square, a window at its width, the output gate's
    projection, a linear mixer's projections, convolution and chunked
    scan, a state-space mixer's likewise, a short convolution's
    projections and taps; of a sparse layer the router, a
    token's picks that land on held experts in expectation and the shared
    expert with its gate.  Under
    diffusion over blocks a token is two rows of every layer (the noisy
    and the clean stream) and one of the head.  An EVA layer: the pairs
    its two masks show a row of a ``max_seq`` sequence, and the pooling.
    The head at its own width, ``pred_heads`` x vocab columns."""
    d, dh = cfg.d_model, cfg.head_dim

    def mixer(kind: LayerKind) -> float:
        m = kind.linear
        if m is not None:
            kw, vw = m.key_width, m.value_width
            return 2 * (d * (2 * kw + 2 * vw + 2 * m.value_heads)
                        + m.conv * (2 * kw + vw) + vw * d
                        + scan_macs_per_token(**m.sizes))
        m = kind.ssm
        if m is not None:
            return 2 * (d * (m.inner + m.conv_width + m.heads)
                        + m.conv * m.conv_width + m.inner * d
                        + ssd_macs_per_token(**m.sizes))
        if kind.conv is not None:
            return 2 * (d * 3 * d + kind.conv.taps * d + d * d)
        h, hk = kind.heads, kind.kv_heads
        gate = {"": 0, "head": h, "elementwise": h * dh}[cfg.out_gate]
        attn_proj = 2 * d * (h * dh + 2 * hk * dh + h * dh + gate)
        attn_scores = 2 * 2 * min(kind.window or cfg.max_seq,
                                  cfg.max_seq) * h * dh     # approx
        if kind.eva is not None:
            pairs = sum(eva_visible_pairs(cfg.max_seq, kind.eva.window,
                                          kind.eva.chunk)) / cfg.max_seq
            # q.k and p.v over the visible pairs; k.phi and the two sums
            attn_scores = 2 * 2 * pairs * h * dh + 2 * 3 * h * dh
        return attn_proj + attn_scores

    def layer(kind: LayerKind) -> float:
        if not kind.sparse:
            return mixer(kind) + 2 * d * kind.d_ff * 3
        moe = cfg.experts
        routed = moe.routed or moe.held
        mats = 3 if moe.gated else 2
        return (mixer(kind) + 2 * d * routed
                + 2 * d * moe.d_ff * mats * moe.per_token * moe.held / routed
                + 2 * d * moe.shared_d_ff * 3 + 2 * d * moe.shared_gate)

    if cfg.period:
        layers = (sum(map(layer, cfg.leading))
                  + cfg.periods * sum(map(layer, cfg.period)))
    else:
        layers = cfg.layers * layer(cfg.uniform_kind)
    return ((2 if cfg.diffusion_block else 1) * layers
            + 2 * d * cfg.vocab * cfg.pred_heads)
