"""Transformer LM pretraining with hybrid parallelism — the flagship demo.

Beyond-reference capability (SURVEY.md §2.7: the reference is DP-only;
this framework's substrate expresses tp/sp/pp/ep natively): one script
that trains the Transformer LM over a 5-axis mesh — data (dp), tensor
(tp), sequence/ring-attention (sp), pipeline (pp), expert (ep) — with the
dp gradient allreduce riding the same fused-collective machinery as every
other example.

CPU simulation of an 8-chip slice:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/jax_transformer_lm.py --dp 2 --tp 2 --pp 2 --steps 5
"""

import argparse
import contextlib
import json
import os
import time

import numpy as np


PRESETS = {
    # BERT-Large-scale pretraining (340M params).
    # The LM objective here is causal rather than MLM; the capability
    # under test — Adasum + wire compression + fused dp allreduce at
    # 24x1024x16 scale — is objective-agnostic.
    "bert-large": dict(layers=24, d_model=1024, heads=16, d_ff=4096,
                       seq=512, vocab=30528, remat=True, loss_chunk=8192),
    # Laguna-XS.2 (poolside, 33.4B-A3B) on one chip's share of an 8-chip
    # layer: the benchmark's configuration laguna_xs2 (cell
    # laguna_xs2_s8192).  Its file holds the model's published config.json
    # keys and, under layers / experts / experts_first / vocab, what this
    # device holds of them: 692M parameters, 10.3 GiB of training state.
    "laguna-xs2": dict(
        published=os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               os.pardir, "benchmark", "configs",
                               "laguna_xs2.json"),
        seq=8192, batch=2, dtype="bfloat16", remat=True, loss_chunk=8192,
        dp=1, tp=1),
    # Qwen3-Next-80B-A3B on one chip's share of a 16-chip layer: the
    # benchmark's configuration qwen3_next_80b (cell qwen3_next_s16384):
    # three Gated DeltaNet layers to one gated full-attention layer, 32 of
    # 512 experts held; 626M parameters, 9.3 GiB of training state.
    "qwen3-next": dict(
        published=os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               os.pardir, "benchmark", "configs",
                               "qwen3_next_80b.json"),
        seq=16384, batch=1, dtype="bfloat16", remat=True, loss_chunk=8192,
        dp=1, tp=1),
    # SDAR-30B-A3B-Chat on one chip's share of an 8-chip layer: the
    # benchmark's configuration sdar_30b_a3b (cell sdar_30b_s8192): six
    # GQA-128 layers with 16 of 128 experts held, trained by diffusion over
    # blocks of 4 tokens (a noisy and a clean stream, 16,384 rows for 8,192
    # tokens); 646M parameters, 9.6 GiB of training state.
    "sdar-30b-a3b": dict(
        published=os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               os.pardir, "benchmark", "configs",
                               "sdar_30b_a3b.json"),
        seq=8192, batch=1, dtype="bfloat16", remat=True, loss_chunk=8192,
        dp=1, tp=1),
    # EvaByte (6.5B, byte-level) on one chip's share of a 4-chip layer: the
    # benchmark's configuration evabyte (cell evabyte_s32768): four layers
    # of EVA attention (exact softmax inside aligned 2,048-byte windows
    # joined with a learned 16-byte chunk summary of every earlier window)
    # with 8 of 32 heads held and the feed-forward whole, 8 prediction
    # heads over 320 ids; 620M parameters, 9.2 GiB of training state.
    "evabyte": dict(
        published=os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               os.pardir, "benchmark", "configs",
                               "evabyte.json"),
        seq=32768, batch=1, dtype="bfloat16", remat=True, loss_chunk=8192,
        dp=1, tp=1),
    # granite-4.0-h-micro (IBM, 3B dense hybrid) as one whole period on one
    # chip: the benchmark's configuration granite_4_0_h_micro (cell
    # granite_h_micro_s8192): nine Mamba-2 state-space layers to one
    # attention layer without a position term, Granite's four multipliers,
    # a tied head over 12,544 of 100,352 rows; 772M parameters, 11.5 GiB of
    # training state.
    "granite_h_micro": dict(
        published=os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               os.pardir, "benchmark", "configs",
                               "granite_4_0_h_micro.json"),
        seq=8192, batch=1, dtype="bfloat16", remat=True, loss_chunk=8192,
        dp=1, tp=1),
    # LFM2-24B-A2B (LiquidAI, 23.8B-A2.3B) as one chip's share of a
    # four-chip layer: the benchmark's configuration lfm2_24b_a2b (cell
    # lfm2_24b_s8192): layers 1-5 of 40 (a leading conv + dense layer, then
    # attention, conv, conv, conv, all sparse), double-gated short
    # convolutions, GQA-64 with q / k norm, top-4 of 64 experts picked by
    # sigmoid score plus a selection bias (16 held), a tied head over 8,192
    # of 65,536 rows; 771M parameters, 11.5 GiB of training state.  (The
    # benchmark's family also ties the router's start over the four ranks
    # and draws the bias; here both start as the published constructor's.)
    "lfm2_24b": dict(
        published=os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               os.pardir, "benchmark", "configs",
                               "lfm2_24b_a2b.json"),
        seq=8192, batch=2, dtype="bfloat16", remat=True, loss_chunk=8192,
        dp=1, tp=1),
}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--dp", type=int, default=2)
    p.add_argument("--tp", type=int, default=2)
    p.add_argument("--pp", type=int, default=1)
    p.add_argument("--sp", type=int, default=1)
    p.add_argument("--ep", type=int, default=1)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--d-ff", type=int, default=512)
    p.add_argument("--vocab", type=int, default=1024)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--batch", type=int, default=8,
                   help="global batch (must divide by dp*pp)")
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--preset", choices=sorted(PRESETS), default=None,
                   help="named model scale (overrides size flags)")
    p.add_argument("--published", default=None, metavar="CONFIG_JSON",
                   help="train the layer-pattern model a published "
                        "config.json describes (models.config_from_"
                        "published): per-kind heads, windows and rotary "
                        "settings, dense and sparse feed-forwards, EVA "
                        "attention, Mamba-2 and short-convolution layers, "
                        "several prediction heads.  The "
                        "file's own layers / layers_first / experts / "
                        "experts_first / "
                        "vocab / heads / heads_first keys, where present, "
                        "cut it to this device's share; the size flags "
                        "are ignored; data-parallel layouts only")
    p.add_argument("--dtype", choices=["float32", "bfloat16"],
                   default="float32",
                   help="activation/compute dtype (bfloat16 on TPU)")
    p.add_argument("--remat", action="store_true",
                   help="jax.checkpoint each block (trade FLOPs for HBM)")
    p.add_argument("--no-remat", action="store_true",
                   help="force remat OFF even when a preset enables it "
                        "(drops the 4/3 recompute; needs the "
                        "activations to fit in HBM — small batch)")
    p.add_argument("--remat-policy", choices=["full", "dots"],
                   default="full",
                   help="full: recompute the whole block; dots: save "
                        "matmul outputs, recompute only elementwise "
                        "(more HBM, no MXU recompute)")
    p.add_argument("--loss-chunk", type=int, default=0,
                   help=">0: chunked-vocab cross entropy (no "
                        "[tokens, vocab] logits tensor)")
    p.add_argument("--use-adasum", action="store_true",
                   help="Adasum gradient combination (dp-only layout)")
    p.add_argument("--bf16-allreduce", action="store_true",
                   help="bf16 wire compression for the dp allreduce "
                        "(dp-only layout)")
    args = p.parse_args()
    if args.preset:
        # Preset fills in only what the user left at parser defaults, so
        # e.g. `--preset bert-large --loss-chunk 0` reproduces the dense
        # loss path at preset scale.
        for k, v in PRESETS[args.preset].items():
            if getattr(args, k) == p.get_default(k):
                setattr(args, k, v)
    if args.no_remat:
        args.remat = False

    import jax
    import jax.numpy as jnp
    import optax
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import (TransformerConfig,
                                    block_diffusion_corrupt,
                                    config_from_published,
                                    transformer_block_diffusion_loss,
                                    transformer_init,
                                    transformer_logical_axes,
                                    transformer_loss,
                                    transformer_flops_per_token)
    from horovod_tpu.parallel import (make_mesh, logical_to_mesh,
                                      transformer_rules)

    explicit_dp = args.use_adasum or args.bf16_allreduce
    if explicit_dp:
        # Adasum / wire compression need the explicit per-rank gradient
        # path (hvd.DistributedOptimizer inside shard_map over dp); the
        # hybrid tp/pp/sp/ep layout leaves the dp reduction to GSPMD
        # instead, where those options don't apply — fold those axes into
        # dp so the flags work from their defaults.
        folded = args.tp * args.pp * args.sp * args.ep
        if folded > 1:
            print(f"note: --use-adasum/--bf16-allreduce use the dp-only "
                  f"layout; folding tp/pp/sp/ep into dp={args.dp * folded}")
            args.dp *= folded
            args.tp = args.pp = args.sp = args.ep = 1

    hvd.init()
    need = args.dp * args.tp * args.pp * args.sp * args.ep
    devs = jax.devices()
    assert len(devs) >= need, f"need {need} devices, have {len(devs)}"
    mesh = make_mesh(devices=devs[:need], dp=args.dp, tp=args.tp,
                     pp=args.pp, sp=args.sp, ep=args.ep)

    if args.published:
        assert args.pp == args.sp == args.ep == 1, \
            "--published runs data-parallel layouts (pp = sp = ep = 1)"
        with open(args.published) as f:
            published = json.load(f)
        cfg = config_from_published(
            published, layers=published.get("layers"),
            layers_first=published.get("layers_first", 0),
            experts=published.get("experts"),
            experts_first=published.get("experts_first", 0),
            vocab=published.get("vocab"),
            heads=published.get("heads"),
            heads_first=published.get("heads_first", 0),
            router_score=published.get("router_score", "sigmoid"),
            # what the model's code does and its config.json has no key
            # for, where the file states it
            shared_gate=published.get("shared_expert_gate", False),
            normalize_eps=published.get("router_normalize_eps", 0.0),
            **{field: published[key] for field, key in (
                ("out_gate", "attn_output_gate"), ("qk_norm", "qk_norm"),
                ("zero_centered_norm", "zero_centered_norm"),
                ("diffusion_block", "block_length"))
               if key in published},
            max_seq=args.seq, dtype=getattr(jnp, args.dtype),
            remat=args.remat, remat_policy=args.remat_policy,
            loss_chunk=args.loss_chunk)
        args.vocab = cfg.vocab          # token ids from the held slice
    else:
        cfg = TransformerConfig(
            vocab=args.vocab, layers=args.layers, d_model=args.d_model,
            heads=args.heads, kv_heads=args.heads, d_ff=args.d_ff,
            max_seq=args.seq, dtype=getattr(jnp, args.dtype),
            num_experts=2 * args.ep if args.ep > 1 else 0,
            sp=args.sp, ep=args.ep, pp=args.pp, remat=args.remat,
            remat_policy=args.remat_policy, loss_chunk=args.loss_chunk)
    lm_loss = transformer_loss
    if cfg.diffusion_block:
        # Diffusion over blocks: the batch is fixed, and so is its noise
        # (one key, drawn on the tokens the loss is given).
        args.vocab = cfg.vocab - 1      # the last held row is the mask token

        def lm_loss(p, tokens, cfg):
            _, t, masked = block_diffusion_corrupt(
                jax.random.PRNGKey(1), tokens, block=cfg.diffusion_block,
                mask_id=cfg.vocab - 1)
            return transformer_block_diffusion_loss(p, tokens, t, masked,
                                                    cfg)

    params = transformer_init(jax.random.PRNGKey(0), cfg)
    rules = transformer_rules()
    axes = transformer_logical_axes(cfg)

    if explicit_dp:
        opt = hvd.DistributedOptimizer(
            optax.adamw(args.lr),
            op=hvd.Adasum if args.use_adasum else hvd.Average,
            compression=(hvd.Compression.bf16 if args.bf16_allreduce
                         else hvd.Compression.none))
    else:
        opt = optax.adamw(args.lr)
    opt_state = opt.init(params)

    # Map stacked-param dims onto manual mesh axes — only axes of size > 1
    # (a size-1 mapping would make params VMA-varying while activations
    # stay invariant, tripping the scan carry type check).
    manual_map = {}
    if args.pp > 1:
        manual_map["stages"] = "pp"
    if args.ep > 1:
        manual_map["experts"] = "ep"

    def manual_spec(tree):
        def keep(lg):
            spec = [manual_map.get(name) for name in lg]
            while spec and spec[-1] is None:
                spec.pop()
            return P(*spec)
        return jax.tree.map(
            keep, tree,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x))

    def _local_loss(p, t):
        l = lm_loss(p, t, cfg)
        varying = tuple(set(jax.typeof(l).vma) & {"pp", "sp", "ep"})
        return lax.pmean(l, varying) if varying else l

    # Open the manual island only over axes with degree > 1: a mesh with
    # pp=sp=ep=1 runs the plain loss under GSPMD-auto sharding, where
    # the flash kernel's shard_map island (over dp/tp) can engage —
    # nesting it inside a size-1 manual island would force the XLA
    # attention fallback (ops/attention.py kernel_plan).
    manual_axes = {ax for ax, d in (("pp", args.pp), ("sp", args.sp),
                                    ("ep", args.ep)) if d > 1}
    island = (jax.shard_map(
        _local_loss, mesh=mesh,
        in_specs=(manual_spec(axes),
                  P(None, "sp") if args.sp > 1 else P()),
        out_specs=P(), axis_names=manual_axes)
        if manual_axes else
        (lambda p, t: lm_loss(p, t, cfg)))

    # Single chip uses the plain loss (no shard_map island) so the
    # Pallas flash path can engage; the hybrid layout differentiates
    # through the island.  One step body serves both.
    def make_step(loss_fn):
        def train_step(params, opt_state, tokens):
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss
        return train_step

    # Single chip defaults to the meshless path (no shard_map island,
    # measured ~5% faster back-to-back).  Flash engages under meshes too
    # now — attention opens a partial-manual shard_map island over the
    # GSPMD-auto axes (ops/attention.py kernel_plan) — so
    # HVDT_LM_SINGLE=0/false/off remains only as the A/B knob for
    # meshless-vs-island compilation (example-local, deliberately not in
    # the framework's config registry).
    single = (need == 1 and not explicit_dp
              and os.environ.get("HVDT_LM_SINGLE", "1").lower()
              not in ("0", "false", "off"))

    # Parameter shardings from logical-axis rules (tp/pp/ep placement).
    if not single:
        param_sh = jax.tree.map(
            lambda lg: NamedSharding(mesh, logical_to_mesh(lg, rules, mesh)),
            axes,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x))
        params = jax.device_put(params, param_sh)
    if explicit_dp:
        def local_step(params, opt_state, tokens):
            def loss_fn(p):
                return lm_loss(p, tokens, cfg)

            # Differentiate w.r.t. VARYING params so AD keeps per-rank
            # gradients and the optimizer's own fused allreduce (with
            # Adasum combine / wire compression) actually runs — with
            # unvarying params AD inserts a plain psum itself and both
            # options would be silently inert (ref:
            # _DistributedAdasumOptimizer, torch/optimizer.py:345).
            diff = hvd.optimizer.pvary_tree(params, "dp")
            loss, grads = jax.value_and_grad(loss_fn)(diff)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, lax.pmean(loss, "dp")

        # hvd.donated_step = jit + donation + the persistent compilation
        # cache (env-transparent via HVDT_COMPILATION_CACHE).
        step = hvd.donated_step(jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(P(), P(), P("dp")),
            out_specs=(P(), P(), P())), donate_argnums=(0, 1))
    elif single:
        step = hvd.donated_step(
            make_step(lambda p, t: lm_loss(p, t, cfg)),
            donate_argnums=(0, 1))
    else:
        step = hvd.donated_step(make_step(island), donate_argnums=(0, 1))

    rng = np.random.default_rng(0)
    tok_sharding = (None if single
                    else NamedSharding(mesh, P("dp", "sp")))

    # One fixed synthetic batch (the synthetic-benchmark convention, ref:
    # pytorch_synthetic_benchmark.py): loss decrease is then deterministic
    # (the model overfits it) and the timed loop has no per-step H2D.
    tokens = jax.device_put(
        rng.integers(0, args.vocab, (args.batch, args.seq),
                     dtype=np.int64).astype(np.int32), tok_sharding)

    # Non-single auto-sharded runs execute under the ambient mesh so the
    # flash kernel's shard_map island sees the auto axes
    # (jax.sharding.get_abstract_mesh in ops/attention.py kernel_plan).
    mesh_ctx = (jax.set_mesh(mesh) if not single and not explicit_dp
                else contextlib.nullcontext())
    with mesh_ctx:
        # Warmup/compile
        params, opt_state, loss = step(params, opt_state, tokens)
        first = float(loss)   # host fetch ends the compile + first step

        t0 = time.perf_counter()
        for _ in range(args.steps):
            params, opt_state, loss = step(params, opt_state, tokens)
        last = float(loss)
        dt = time.perf_counter() - t0

    tokens_sec = args.steps * args.batch * args.seq / dt
    tflops = (3 * transformer_flops_per_token(cfg) * tokens_sec) / 1e12
    if hvd.rank() == 0:
        print(f"mesh={dict(mesh.shape)}")
        print(f"loss: {first:.4f} -> {last:.4f}")
        print(f"{tokens_sec:.0f} tokens/sec, ~{tflops:.3f} model TFLOP/s")
        assert last < first, "loss should decrease"

    if hvd.rank() == 0:
        print("done.")


if __name__ == "__main__":
    main()
