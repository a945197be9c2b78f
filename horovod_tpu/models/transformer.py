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
  pp wraps the block stack in ``pipeline_spmd``.

The reference has no model layer — its examples lean on torchvision/Keras
(ref: examples/pytorch/pytorch_synthetic_benchmark.py:17-26).  This module
is the equivalent benchmark substrate plus the TP/SP/PP/EP showcase the
reference lacks (SURVEY.md §2.7).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax import lax

from ..ops.attention import attention
from ..parallel.moe import moe_dispatch_combine
from ..parallel.pipeline import pipeline_spmd
from ..parallel.ring_attention import ring_attention
from ..quant import fp8 as _fp8

__all__ = [
    "TransformerConfig", "transformer_init", "transformer_apply",
    "transformer_loss", "transformer_logical_axes",
    "transformer_flops_per_token", "remat_from_env", "checkpoint_policy",
    "transformer_decode_paged", "transformer_prefill_paged",
    "transformer_prefill_collect",
]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
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
    #   "full" — save only block inputs, recompute everything (min HBM,
    #            +1/3 FLOPs — the classic trade);
    #   "dots" — jax.checkpoint_policies.dots_with_no_batch_dims_saveable:
    #            save non-batched matmul outputs (projections, FF), so
    #            the backward recomputes only cheap elementwise work and
    #            attention scores.  ~MXU-free recompute at the cost of
    #            O(layers * 6*b*l*d + b*l*4d) extra HBM residency.
    # (An "attn" policy saving each block's attention output was measured
    # and REMOVED: saving attention's output cannot skip recomputing its
    # internals — the VJP still needs q/k/v/scores — so it bought 1.3%
    # of grad FLOPs for ~3.2 GB extra residency and OOM'd the BERT-Large
    # bs128 config.)
    remat_policy: str = "full"
    loss_chunk: int = 0          # >0: chunked-vocab cross entropy

    @property
    def head_dim(self) -> int:
        return self.d_model // self.heads

    @property
    def layers_per_stage(self) -> int:
        assert self.layers % max(self.pp, 1) == 0
        return self.layers // max(self.pp, 1)


def _init_linear(key, fan_in, shape, dtype):
    return (jax.random.normal(key, shape) * (fan_in ** -0.5)).astype(dtype)


def transformer_init(key: jax.Array, cfg: TransformerConfig) -> Dict:
    """Parameter pytree. Block params are stacked [layers, ...] for scan;
    under pp they are reshaped to [pp, layers_per_stage, ...] at apply time
    (same memory layout, stage-major)."""
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
    maps to "stages" so pp shards it when the mesh has a pp axis."""
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


def _rmsnorm(x, g):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + 1e-6)).astype(
        x.dtype) * g.astype(x.dtype)


def _rope(x, positions, theta):
    """x: [B, L, H, D]; positions: [B, L] global token positions."""
    d2 = x.shape[-1] // 2
    freqs = (1.0 / theta) ** (jnp.arange(d2, dtype=jnp.float32) / d2)
    ang = positions[..., None].astype(jnp.float32) * freqs   # [B, L, d2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).astype(x.dtype)


def _proj(x, w):
    """Dense projection ``x @ w`` in the activation dtype — rides the
    per-tensor-scaled fp8 (e4m3) convert-dot when ``HVDT_FP8=matmul``
    and the backend supports it (quant/fp8.py); otherwise exactly the
    plain matmul.  The gate is resolved at trace time from env config,
    so flipping HVDT_FP8 recompiles rather than branching in-graph."""
    if _fp8.matmul_enabled():
        return _fp8.fp8_matmul(x, w)
    return x @ w.astype(x.dtype)


def _qkv(p, x, positions, cfg: TransformerConfig):
    """Rotated q/k/v projections — the one place the projection + RoPE
    recipe lives, shared by training attention (:func:`_attention`) and
    the serving paged-KV prefill/decode paths, so the cache can never
    hold keys rotated differently from the ones training computed."""
    b, l, _ = x.shape
    h, hk, dh = cfg.heads, cfg.kv_heads, cfg.head_dim
    q = _proj(x, p["wq"]).reshape(b, l, h, dh)
    k = _proj(x, p["wk"]).reshape(b, l, hk, dh)
    v = _proj(x, p["wv"]).reshape(b, l, hk, dh)
    q = _rope(q, positions, cfg.rope_theta)
    k = _rope(k, positions, cfg.rope_theta)
    return q, k, v


def _attention(p, x, positions, cfg: TransformerConfig):
    b, l, _ = x.shape
    q, k, v = _qkv(p, x, positions, cfg)
    if cfg.sp > 1:
        # Manual island: the sequence dim is the local sp shard here (the
        # caller's shard_map over {'sp'} has already split it).
        o = ring_attention(q, k, v, axis="sp", causal=True)
    else:
        o = attention(q, k, v)
    return _proj(o.reshape(b, l, cfg.heads * cfg.head_dim), p["wo"])


def _mlp(p, x):
    up = _proj(x, p["w_up"])
    gate = jax.nn.silu(_proj(x, p["w_gate"]))
    return _proj(up * gate, p["w_down"])


def _moe_mlp(p, x, cfg: TransformerConfig):
    b, l, d = x.shape
    tokens = x.reshape(b * l, d)
    logits = tokens @ p["w_router"].astype(x.dtype)
    w_up, w_down = p["w_up"].astype(x.dtype), p["w_down"].astype(x.dtype)
    if cfg.ep > 1:
        # w_up/w_down enter the island sharded over ep on the expert dim.
        def expert_fn(toks):   # [E_local, N, D]
            hmid = jax.nn.silu(jnp.einsum("end,edf->enf", toks, w_up))
            return jnp.einsum("enf,efd->end", hmid, w_down)
        out, aux = moe_dispatch_combine(
            tokens, logits, expert_fn, axis="ep",
            experts_per_rank=cfg.num_experts // cfg.ep,
            capacity_factor=cfg.capacity_factor)
    else:
        # Dense (einsum-over-experts) fallback: exact, no capacity drops.
        probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
        top = jnp.argmax(probs, -1)
        gate = jnp.take_along_axis(probs, top[:, None], 1)[:, 0]
        hmid = jax.nn.silu(jnp.einsum("nd,edf->enf", tokens, w_up))
        all_out = jnp.einsum("enf,efd->end", hmid, w_down)
        sel = jnp.take_along_axis(
            all_out, top[None, :, None], 0)[0]
        out = sel * gate[:, None].astype(x.dtype)
        aux = None
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
    the memory-for-MFU trade surfaced as ``bench.py --remat`` /
    ``hvdtrun --remat``.  Returns ``cfg`` unchanged for ``none`` (and
    the ``dots``→``full`` fallback is resolved here so the config names
    the policy that will actually run)."""
    pol = checkpoint_policy(mode)
    if pol is None:
        return dataclasses.replace(cfg, remat=False)
    policy_name = "full" if pol == "full" else "dots"
    return dataclasses.replace(cfg, remat=True, remat_policy=policy_name)


def _block(p, x, positions, cfg: TransformerConfig):
    # Each sublayer with its pre-norm under one name, for the profiler
    # and the benchmark's phase split (docs/observability.md).
    with jax.named_scope("hvdt.attention"):
        a = _attention(p, _rmsnorm(x, p["ln1"]), positions, cfg)
    x = x + a
    with jax.named_scope("hvdt.mlp"):
        if cfg.num_experts:
            y, _ = _moe_mlp(p, _rmsnorm(x, p["ln2"]), cfg)
        else:
            y = _mlp(p, _rmsnorm(x, p["ln2"]))
    return x + y


def _scan_blocks(block_params, x, positions, cfg: TransformerConfig):
    body = functools.partial(_block, positions=positions, cfg=cfg)
    if cfg.remat:
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
                body = jax.checkpoint(body)
            else:
                body = jax.checkpoint(body, policy=pol)
        elif cfg.remat_policy == "full":
            body = jax.checkpoint(body)
        else:
            raise ValueError(
                f"unknown remat_policy {cfg.remat_policy!r} "
                "(expected 'full' or 'dots')")

    def step(h, layer_p):
        return body(layer_p, h), None

    out, _ = lax.scan(step, x, block_params)
    return out


def transformer_hidden(params: Dict, tokens: jax.Array,
                       cfg: TransformerConfig) -> jax.Array:
    """Final-norm hidden states [batch, seq, d_model] (everything but the
    vocab projection — split out so the chunked loss can avoid ever
    materializing [batch, seq, vocab] logits).

    tokens: [batch, seq] int32 — the *local* sp shard of the sequence when
    called inside a shard_map over {'sp'} (positions are globalized with
    the sp rank), the full sequence otherwise.
    """
    b, l = tokens.shape
    if cfg.sp > 1:
        offset = lax.axis_index("sp") * l
    else:
        offset = 0
    positions = offset + jnp.broadcast_to(jnp.arange(l), (b, l))
    x = params["embed"].astype(cfg.dtype)[tokens]
    # Manual-island axes make activations varying (e.g. the MoE alltoall);
    # pre-cast so the scan-over-layers carry is type-stable under vma.
    from ..parallel.sharding import pcast_to_union

    manual_axes = [ax for ax, on in (("sp", cfg.sp > 1),
                                     ("ep", cfg.ep > 1 and cfg.num_experts))
                   if on]
    x = pcast_to_union(x, extra=tuple(manual_axes))
    if cfg.pp > 1:
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

        x = pipeline_spmd(stage_fn, params["block"], acts, axis="pp")
        x = x.reshape(b, l, cfg.d_model)
    else:
        # Block params may still be varying on manual axes the config
        # doesn't know about (e.g. a stages dim spec'd onto a size-1 pp
        # mesh axis); the scan carry must match, so pcast x up to the
        # union of the params' varying axes.
        from ..parallel.sharding import pcast_to_union

        x = pcast_to_union(x, *jax.tree.leaves(params["block"]))
        x = _scan_blocks(params["block"], x, positions, cfg)
    return _rmsnorm(x, params["ln_f"])


def transformer_apply(params: Dict, tokens: jax.Array,
                      cfg: TransformerConfig) -> jax.Array:
    """Logits for next-token prediction (see transformer_hidden)."""
    return _head(params, transformer_hidden(params, tokens, cfg))


def _head(params: Dict, x: jax.Array) -> jax.Array:
    """The tied output projection: hidden states to f32 logits."""
    return (x @ params["embed"].astype(x.dtype).T).astype(jnp.float32)


def _chunked_xent(x: jax.Array, embed: jax.Array, targets: jax.Array,
                  chunk: int) -> jax.Array:
    """Cross entropy without the [tokens, vocab] logits: scan over vocab
    chunks with an online logsumexp, checkpointed so the backward pass
    recomputes each chunk's logits instead of saving them.  Peak memory
    per step drops from O(tokens x vocab) f32 to O(tokens x chunk) —
    the lever that lets BERT-Large-scale batches fit in HBM (measured:
    dense f32 logits at batch 128 x seq 512 x 30k vocab are 8 GB alone).
    Numerics match the dense path up to fp reassociation."""
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
    return (jnp.log(s) + m - tl).mean()


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
    (no [tokens, vocab] logits tensor)."""
    targets = tokens[:, 1:]
    x = transformer_hidden(params, tokens, cfg)
    # The tied head's matmul is inside the scope on both branches.
    with jax.named_scope("hvdt.loss"):
        if cfg.loss_chunk:
            return _chunked_xent(x[:, :-1], params["embed"], targets,
                                 cfg.loss_chunk)
        logits = _head(params, x)[:, :-1]
        logp = jax.nn.log_softmax(logits, -1)
        ll = jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
        return -ll.mean()


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
    """Approximate forward-pass matmul FLOPs per token (for MFU metrics)."""
    d, f, l = cfg.d_model, cfg.d_ff, cfg.layers
    h, hk, dh = cfg.heads, cfg.kv_heads, cfg.head_dim
    attn_proj = 2 * d * (h * dh + 2 * hk * dh + h * dh)
    attn_scores = 2 * 2 * cfg.max_seq * h * dh          # per token, approx
    mlp = 2 * d * f * (3 if not cfg.num_experts else 2)
    return l * (attn_proj + attn_scores + mlp) + 2 * d * cfg.vocab
