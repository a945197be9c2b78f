"""Model zoo for benchmarks, examples, and the driver's flagship entry.

The reference ships its models as examples (ref: examples/pytorch/
pytorch_synthetic_benchmark.py — torchvision resnet50;
examples/tensorflow2/tensorflow2_synthetic_benchmark.py — Keras ResNet50;
examples/pytorch/pytorch_mnist.py).  Here the models are first-class,
pure-JAX pytree models designed to compose with the parallelism substrate
(``horovod_tpu.parallel``): logical-axis annotations per parameter, ring
attention over ``sp``, MoE over ``ep``, pipeline stacking over ``pp``.
"""

from .transformer import (  # noqa: F401
    TransformerConfig,
    LayerKind,
    LinearMixer,
    StateSpaceMixer,
    ShortConv,
    Eva,
    Rope,
    Experts,
    config_from_published,
    transformer_init,
    transformer_apply,
    transformer_loss,
    transformer_block_diffusion_loss,
    block_diffusion_corrupt,
    transformer_logical_axes,
    transformer_flops_per_token,
    remat_from_env,
    checkpoint_policy,
)
from .resnet import (  # noqa: F401
    ResNetConfig,
    resnet50_init,
    resnet101_init,
    resnet_apply,
    resnet_loss,
)
from .vgg import (  # noqa: F401
    VGGConfig,
    vgg16_init,
    vgg_apply,
    vgg_loss,
)
from .mlp import mlp_init, mlp_apply, mlp_loss  # noqa: F401
