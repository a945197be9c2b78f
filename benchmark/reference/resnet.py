"""Plain reference for the ``resnet`` family: ResNet (bottleneck, v1.5 —
the stride sits on the 3x3 convolution, as torchvision's ``resnet50``
builds it) in straightforward ``jax.numpy`` / ``lax.conv``: float32
throughout, ``jax.default_matmul_precision("highest")``, the literal 7x7
stem, batch normalisation in its textbook form (y = (x - mean) /
sqrt(var + eps) * scale + bias with the biased batch variance), no fused
kernels and no folded coefficients.  It imports nothing from
``horovod_tpu`` and reads the same parameter pytree the system trains.

Departures from the paper (He et al. 2015), all of them the system's and
so the reference's: NHWC layout; TensorFlow-style "SAME" padding (a
stride-2 3x3 window over an even side pads (0, 1), where torchvision pads
(1, 1)); training-mode batch statistics with no running-average update in
the loss.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5
STAGES = {26: (1, 1, 1, 1), 50: (3, 4, 6, 3), 101: (3, 4, 23, 3)}


def conv(x, w, stride=1, padding="SAME"):
    return lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def batch_norm(x, p):
    mean = x.mean((0, 1, 2))
    var = ((x - mean) ** 2).mean((0, 1, 2))
    return (x - mean) / jnp.sqrt(var + BN_EPS) * p["scale"] + p["bias"]


def bottleneck(x, p, stride):
    y = jax.nn.relu(batch_norm(conv(x, p["conv1"]), p["bn1"]))
    y = jax.nn.relu(batch_norm(conv(y, p["conv2"], stride), p["bn2"]))
    y = batch_norm(conv(y, p["conv3"]), p["bn3"])
    if "conv_proj" in p:
        x = batch_norm(conv(x, p["conv_proj"], stride), p["bn_proj"])
    return jax.nn.relu(y + x)


def loss(params, images, labels, *, depth: int = 50):
    """Mean cross entropy of ``images`` [N, H, W, 3] against ``labels``."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = conv(images.astype(jnp.float32), params["conv_stem"], 2,
                 [(3, 3), (3, 3)])
        x = jax.nn.relu(batch_norm(x, params["bn_stem"]))
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
        for si, blocks in enumerate(STAGES[depth]):
            for bi in range(blocks):
                x = bottleneck(x, params[f"s{si}b{bi}"],
                               2 if (bi == 0 and si > 0) else 1)
        logits = x.mean((1, 2)) @ params["fc_w"] + params["fc_b"]
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.take_along_axis(logp, labels[:, None], -1).mean()
