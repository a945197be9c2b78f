"""ResNet-50 (v1.5), pure-JAX pytree implementation.

The reference benchmarks Horovod with torchvision/Keras ResNet-50
(ref: examples/pytorch/pytorch_synthetic_benchmark.py:17-26,
examples/tensorflow2/tensorflow2_synthetic_benchmark.py; docs/benchmarks.rst
headline numbers — SURVEY.md §6).  This is the equivalent model for this
framework's synthetic benchmark (``benchmark/``, cell ``resnet50_train``;
``examples/jax_synthetic_benchmark.py``).

TPU-first choices: NHWC layout (XLA-TPU native), bf16 compute with f32
batch-norm statistics, ``(params, batch_stats)`` as explicit pytrees so
the train step is a pure function.  Cross-replica BN is available via
``horovod_tpu.sync_batch_norm`` semantics: pass ``bn_axis`` to average
batch statistics over the data-parallel mesh axis (the reference's
SyncBatchNorm, ref: torch/sync_batch_norm.py:1-218).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["ResNetConfig", "resnet50_init", "resnet101_init",
           "resnet_apply", "resnet_loss"]

# Stage layouts: (blocks, mid-channels) per stage.  ResNet-101 is the
# reference's published benchmark model (docs/benchmarks.rst:27-43 —
# 1656.82 img/s over 16 P100s); ResNet-50 is its synthetic-benchmark
# default (examples/pytorch/pytorch_synthetic_benchmark.py:17-26).
_STAGES = {
    # Minimal bottleneck layout (ResNet-26): one block per stage — same
    # stem/BN/downsample plumbing as 50/101 at a fraction of the compile
    # time; used by tests that probe plumbing rather than capacity.
    26: ((1, 64), (1, 128), (1, 256), (1, 512)),
    50: ((3, 64), (4, 128), (6, 256), (3, 512)),
    101: ((3, 64), (4, 128), (23, 256), (3, 512)),
}


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    num_classes: int = 1000
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    bn_momentum: float = 0.9
    bn_eps: float = 1e-5
    bn_axis: Optional[str] = None   # mesh axis for cross-replica SyncBN
    # Rematerialization of the per-block BN/relu epilogues: "epilogue"
    # saves ONLY conv outputs for the backward pass and recomputes the
    # (cheap, elementwise) BN+relu from them.  Cuts peak activation
    # memory ~2x for batch scaling, but measured SLOWER on v5e at bs128
    # (2324 vs 2705 img/s — the recompute pass re-reads conv outputs, a
    # net traffic add on an HBM-bound step), so the default is "none".
    remat: str = "none"
    # Stem lowering: "s2d" rewrites the 7x7/2 stem conv as an exactly
    # equivalent space-to-depth(2) + 4x4/1 conv (the MLPerf-TPU stem
    # trick): C_in goes 3 -> 12, quartering the MXU lane padding waste of
    # a 3-channel conv and shrinking the 224x224 input slicing XLA
    # otherwise does.  "conv" keeps the literal 7x7 conv.
    stem: str = "s2d"
    depth: int = 50              # 26, 50 or 101 (bottleneck stage layouts)


def _conv_init(key, kh, kw, cin, cout, dtype):
    fan_in = kh * kw * cin
    return (jax.random.normal(key, (kh, kw, cin, cout))
            * np.sqrt(2.0 / fan_in)).astype(dtype)


def _bn_init(c, dtype):
    return {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)}


def _bn_stats(c):
    return {"mean": jnp.zeros((c,), jnp.float32),
            "var": jnp.ones((c,), jnp.float32)}


def resnet50_init(key: jax.Array, cfg: ResNetConfig
                  ) -> Tuple[Dict, Dict]:
    """Returns (params, batch_stats) for the cfg's depth (50 default)."""
    pd = cfg.param_dtype
    stages = _STAGES[cfg.depth]
    n_blocks = sum(b for b, _ in stages)
    keys = iter(jax.random.split(key, 4 + n_blocks * 4))
    params: Dict = {"conv_stem": _conv_init(next(keys), 7, 7, 3, 64, pd),
                    "bn_stem": _bn_init(64, pd)}
    stats: Dict = {"bn_stem": _bn_stats(64)}
    cin = 64
    for si, (blocks, mid) in enumerate(stages):
        cout = mid * 4
        for bi in range(blocks):
            name = f"s{si}b{bi}"
            p = {
                "conv1": _conv_init(next(keys), 1, 1, cin, mid, pd),
                "bn1": _bn_init(mid, pd),
                "conv2": _conv_init(next(keys), 3, 3, mid, mid, pd),
                "bn2": _bn_init(mid, pd),
                "conv3": _conv_init(next(keys), 1, 1, mid, cout, pd),
                "bn3": _bn_init(cout, pd),
            }
            s = {"bn1": _bn_stats(mid), "bn2": _bn_stats(mid),
                 "bn3": _bn_stats(cout)}
            if bi == 0:
                p["conv_proj"] = _conv_init(next(keys), 1, 1, cin, cout, pd)
                p["bn_proj"] = _bn_init(cout, pd)
                s["bn_proj"] = _bn_stats(cout)
            params[name] = p
            stats[name] = s
            cin = cout
    params["fc_w"] = (jax.random.normal(next(keys), (cin, cfg.num_classes))
                      * (cin ** -0.5)).astype(pd)
    params["fc_b"] = jnp.zeros((cfg.num_classes,), pd)
    return params, stats


def resnet101_init(key: jax.Array, cfg: ResNetConfig
                   ) -> Tuple[Dict, Dict]:
    """ResNet-101 (the reference's published benchmark model,
    ref: docs/benchmarks.rst:27-43).  Returns (params, batch_stats).

    Requires ``cfg.depth == 101``: ``resnet_apply`` walks the stage
    layout from the SAME cfg, so silently patching depth here would
    leave the caller applying a ResNet-50 subgraph over 101's params."""
    if cfg.depth != 101:
        raise ValueError(
            f"resnet101_init needs ResNetConfig(depth=101), got "
            f"depth={cfg.depth} — resnet_apply uses cfg.depth too")
    return resnet50_init(key, cfg)


def _conv(x, w, stride=1):
    y = lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    # Tag conv outputs as the residency boundary for the "epilogue" remat
    # policy (see ResNetConfig.remat).
    return jax.ad_checkpoint.checkpoint_name(y, "rn_conv_out")


def _stem_conv(x, w, cfg: ResNetConfig):
    """The 7x7/2 stem, pad (3,3) — lowered per ``cfg.stem``.

    "s2d" is the exact space-to-depth rewrite: with y[i] reading input
    rows 2i-3..2i+3, pack row pairs into channels (xs[p, (dy,dx,k)] =
    x[2p+dy, 2q+dx, k], 224^2x3 -> 112^2x12) and convolve with the 4x4
    repack of the 7x7 kernel, W4[u,v,(dy,dx,k),c] = w[2u+dy-1, 2v+dx-1,
    k, c] (zero where the index underflows), stride 1, pad (2,1).  Same
    sum, identical output; the MXU sees C_in=12 instead of 3."""
    w = w.astype(x.dtype)
    # s2d needs even H/W for the 2x2 pixel packing; odd sizes (e.g.
    # --image-size 225) take the literal conv.
    if cfg.stem != "s2d" or x.shape[1] % 2 or x.shape[2] % 2:
        return lax.conv_general_dilated(
            x, w, (2, 2), [(3, 3), (3, 3)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    n, h, wd, c = x.shape
    xs = x.reshape(n, h // 2, 2, wd // 2, 2, c).transpose(0, 1, 3, 2, 4, 5)
    xs = xs.reshape(n, h // 2, wd // 2, 4 * c)
    wp = jnp.pad(w, ((1, 0), (1, 0), (0, 0), (0, 0)))
    w4 = wp.reshape(4, 2, 4, 2, c, w.shape[-1]).transpose(0, 2, 1, 3, 4, 5)
    w4 = w4.reshape(4, 4, 4 * c, w.shape[-1])
    return lax.conv_general_dilated(
        xs, w4, (1, 1), [(2, 1), (2, 1)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _batch_norm(x, p, s, cfg: ResNetConfig, train: bool):
    """Returns (y, new_stats). In training mode uses batch statistics
    (optionally averaged over ``cfg.bn_axis`` — SyncBatchNorm) and
    EMA-updates the running stats."""
    if train:
        axes = (0, 1, 2)
        # f32 upcast + square fuse into the reduction pass (reads bf16
        # from HBM, accumulates f32 — no materialized f32 copy).
        xf = x.astype(jnp.float32)
        if cfg.bn_axis is not None:
            # Sync the MOMENTS, then form the variance (the one shared
            # implementation — sync_batch_norm.sync_batch_stats):
            # pmean'ing per-device variances would drop the
            # between-device mean-variance term, undershooting the
            # exact global var by Var_devices(mean_d).
            from ..sync_batch_norm import sync_batch_stats

            mean, var = sync_batch_stats(xf, cfg.bn_axis,
                                         reduction_axes=axes)
        else:
            mean = xf.mean(axes)
            var = (xf ** 2).mean(axes) - mean ** 2
        m = cfg.bn_momentum
        new_s = {"mean": m * s["mean"] + (1 - m) * mean,
                 "var": m * s["var"] + (1 - m) * var}
    else:
        mean, var = s["mean"], s["var"]
        new_s = s
    # Fold (mean, var, scale, bias) into one per-channel FMA applied in the
    # activation dtype: stats/coefficients stay f32 (reduction precision) but
    # the [N,H,W,C] elementwise work is y = x*a + b in bf16, which XLA fuses
    # as a conv epilogue without materializing f32 activation copies — this
    # is the HBM-traffic lever on v5e (the f32 normalize chain cost ~8 bytes
    # per element per pass vs 2 here).
    inv = lax.rsqrt(var + cfg.bn_eps)
    a = (p["scale"].astype(jnp.float32) * inv).astype(x.dtype)
    b = (p["bias"].astype(jnp.float32)
         - mean * p["scale"].astype(jnp.float32) * inv).astype(x.dtype)
    return x * a + b, new_s


def _fused_1x1_eligible(w, stride, cfg, x=None) -> bool:
    """HVDT_FUSED_CONV1X1 gate: fused Pallas conv+BN for 1x1 stride-1
    convs with 128-lane-tiling output channels.  SyncBN (cfg.bn_axis)
    is supported — the kernel's per-device stat partials are psum'd
    over the axis (ops/conv_fused.conv1x1_bn_train(axis=...)).

    When ``x`` is given, also gate on the matmul's M = B*H*W rows
    tiling: the kernel's row blocks must clear the per-dtype sublane
    floor (8 rows f32 / 16 bf16 / 32 one-byte), so an M whose largest
    power-of-2 divisor is smaller (e.g. batch 1 at 14x14 → M=196 → 4)
    falls back to the XLA conv path instead of crashing in
    ops/conv_fused._fit_block at trace time (ADVICE r5)."""
    from ..common import config

    kh, kw, cin, cout = w.shape
    if x is not None:
        m = x.shape[0] * x.shape[1] * x.shape[2]
        floor = {4: 8, 2: 16, 1: 32}.get(jnp.dtype(x.dtype).itemsize, 8)
        if (m & -m) < floor:     # largest power-of-2 divisor of M
            return False
    # cin gate too: K=64 lane tiles (stage-0 blocks, 64->256) are
    # outside every probe-validated shape — keep them on XLA until a
    # probe shape covers them.
    return (config.get_bool("HVDT_FUSED_CONV1X1") and kh == 1 and kw == 1
            and stride == 1 and cout % 128 == 0 and cin % 128 == 0)


def _conv_bn(x, w, bn_p, bn_s, cfg, train, *, stride=1, relu=False):
    """conv + BN (+ReLU) — one call site shape for both the XLA path
    and the fused Pallas path (ops/conv_fused.py), so the A/B differs
    only in lowering.  One documented exception to exact gradient
    equality: the fused kernel uses relu'(0)=0 while jnp.maximum's
    autodiff splits the tie at 0.5, so units with EXACTLY zero
    pre-activation (measure zero under random inputs) get different
    subgradients.  Returns (y, new_bn_stats)."""
    if _fused_1x1_eligible(w, stride, cfg, x):
        from ..ops.conv_fused import conv1x1_bn_relu, conv1x1_bn_train

        w2 = w.reshape(w.shape[2], w.shape[3]).astype(x.dtype)
        if train:
            y, mean, var = conv1x1_bn_train(
                x, w2, bn_p["scale"], bn_p["bias"], eps=cfg.bn_eps,
                relu=relu, axis=cfg.bn_axis)
            m = cfg.bn_momentum
            new_s = {"mean": m * bn_s["mean"] + (1 - m) * mean,
                     "var": m * bn_s["var"] + (1 - m) * var}
        else:
            inv = lax.rsqrt(bn_s["var"] + cfg.bn_eps)
            scale = bn_p["scale"].astype(jnp.float32) * inv
            bias = (bn_p["bias"].astype(jnp.float32)
                    - bn_s["mean"] * scale)
            y = conv1x1_bn_relu(x, w2, scale, bias, relu=relu)
            new_s = bn_s
        # Same residency anchor as _conv, so the "epilogue" remat
        # policy keeps a boundary here on the fused path too.
        return jax.ad_checkpoint.checkpoint_name(y, "rn_conv_out"), new_s
    y, new_s = _batch_norm(_conv(x, w, stride), bn_p, bn_s, cfg, train)
    if relu:
        y = jax.nn.relu(y)
    return y, new_s


def _bottleneck(x, p, s, cfg, train, stride):
    out_s = {}
    y, out_s["bn1"] = _conv_bn(x, p["conv1"], p["bn1"], s["bn1"], cfg,
                               train, relu=True)
    # v1.5: stride lives on the 3x3 conv.
    y, out_s["bn2"] = _conv_bn(y, p["conv2"], p["bn2"], s["bn2"], cfg,
                               train, stride=stride, relu=True)
    y, out_s["bn3"] = _conv_bn(y, p["conv3"], p["bn3"], s["bn3"], cfg,
                               train, relu=False)
    if "conv_proj" in p:
        sc, out_s["bn_proj"] = _conv_bn(x, p["conv_proj"], p["bn_proj"],
                                        s["bn_proj"], cfg, train,
                                        stride=stride, relu=False)
    else:
        sc = x
    return jax.nn.relu(y + sc), out_s


def resnet_apply(params: Dict, batch_stats: Dict, images: jax.Array,
                 cfg: ResNetConfig, train: bool = True
                 ) -> Tuple[jax.Array, Dict]:
    """images: [N, H, W, 3] → (logits [N, classes], new_batch_stats)."""
    x = images.astype(cfg.dtype)
    new_stats: Dict = {}
    x = _stem_conv(x, params["conv_stem"], cfg)
    x, new_stats["bn_stem"] = _batch_norm(
        x, params["bn_stem"], batch_stats["bn_stem"], cfg, train)
    x = jax.nn.relu(x)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    def _block(x, p, s, stride):
        return _bottleneck(x, p, s, cfg, train, stride)

    if cfg.remat == "epilogue":
        policy = jax.checkpoint_policies.save_only_these_names("rn_conv_out")
        block = jax.checkpoint(_block, policy=policy, static_argnums=(3,))
    else:
        block = _block
    for si, (blocks, _) in enumerate(_STAGES[cfg.depth]):
        for bi in range(blocks):
            name = f"s{si}b{bi}"
            stride = 2 if (bi == 0 and si > 0) else 1
            x, new_stats[name] = block(
                x, params[name], batch_stats[name], stride)
    x = x.mean(axis=(1, 2)).astype(jnp.float32)
    logits = x @ params["fc_w"].astype(jnp.float32) + params["fc_b"].astype(
        jnp.float32)
    return logits, new_stats


def resnet_loss(params: Dict, batch_stats: Dict, images: jax.Array,
                labels: jax.Array, cfg: ResNetConfig
                ) -> Tuple[jax.Array, Dict]:
    """Cross-entropy loss; returns (loss, new_batch_stats) for
    ``jax.value_and_grad(..., has_aux=True)``."""
    logits, new_stats = resnet_apply(params, batch_stats, images, cfg, True)
    logp = jax.nn.log_softmax(logits, -1)
    loss = -jnp.take_along_axis(logp, labels[:, None], -1).mean()
    return loss, new_stats
