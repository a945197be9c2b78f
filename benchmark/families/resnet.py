"""The ``resnet`` family: the repo's bottleneck ResNet
(``horovod_tpu.models.resnet``) under softmax cross entropy."""

from __future__ import annotations

import functools

from benchmark.families import Family, dtype_of, make_optimizer
from benchmark.reference import resnet as reference

_MID = (64, 128, 256, 512)


def conv_shapes(config: dict):
    """Every convolution of the network, in order, as
    ``(kh, kw, cin, cout, out_side, in_side)`` for one image."""
    side = config["image_size"] // 2
    shapes = [(7, 7, 3, 64, side, config["image_size"])]
    side //= 2                                        # max-pool
    cin = 64
    for si, blocks in enumerate(reference.STAGES[config["depth"]]):
        mid, cout = _MID[si], _MID[si] * 4
        for bi in range(blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            out = side // stride
            shapes.append((1, 1, cin, mid, side, side))
            shapes.append((3, 3, mid, mid, out, side))
            shapes.append((1, 1, mid, cout, out, out))
            if bi == 0:
                shapes.append((1, 1, cin, cout, out, side))
            side, cin = out, cout
    return shapes


def flops_per_image(config: dict) -> float:
    """Forward + backward operations per image, from shapes: 2 per
    multiply-add; each convolution's backward is an input-gradient and a
    weight-gradient convolution of the forward's size, except the stem,
    whose input gradient nothing needs; the classifier likewise."""
    shapes = conv_shapes(config)
    per_conv = [2.0 * kh * kw * cin * cout * out * out
                for kh, kw, cin, cout, out, _ in shapes]
    fc = 2.0 * shapes[-1][3] * config["num_classes"]
    return per_conv[0] * 2 + sum(per_conv[1:]) * 3 + fc * 3


def build(config: dict, traffic: dict) -> Family:
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import ResNetConfig, resnet50_init, resnet_loss

    dtype = dtype_of(config["compute_dtype"])
    cfg = ResNetConfig(num_classes=config["num_classes"], dtype=dtype,
                       param_dtype=dtype_of(config["param_dtype"]),
                       depth=config["depth"])
    size = config["image_size"]
    # Running statistics are not trained and do not enter the loss; the
    # initial ones are closed over, as chip_smoke.phase_resnet does.
    stat_shapes = jax.eval_shape(lambda k: resnet50_init(k, cfg)[1],
                                 jax.random.PRNGKey(0))

    def loss_fn(params, images, labels):
        stats = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             stat_shapes)
        return resnet_loss(params, stats, images, labels, cfg)[0]

    def make_batch(key, samples):
        k1, k2 = jax.random.split(key)
        return (jax.random.normal(k1, (samples, size, size, 3), dtype),
                jax.random.randint(k2, (samples,), 0, cfg.num_classes))

    return Family(
        init=lambda key: resnet50_init(key, cfg)[0],
        loss_fn=loss_fn,
        optimizer=make_optimizer(config["optimizer"]),
        make_batch=make_batch,
        unit="images",
        units_per_sample=1,
        flops_per_unit=flops_per_image(config),
        sample_size=traffic.get("sample_images", 8),
        reference_loss=functools.partial(reference.loss,
                                         depth=config["depth"]),
        sample_env=lambda mosaic: {},
        tolerances=config["tolerances"])
