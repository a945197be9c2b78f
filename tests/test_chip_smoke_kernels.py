"""chip_smoke.py's kernel and ResNet phases at toy size on the CPU
simulator (the LM phases and the script's own exits:
tests/test_chip_smoke.py).  Split from it so that neither file is a
worker's whole share of the run under --dist loadfile."""

import os

from test_chip_smoke import REPO, cs, mesh4  # noqa: F401  (fixtures)

TOY_KERNELS = dict(
    kernel_flash_forward=dict(batch=1, seq=256, heads=2, head_dim=64),
    kernel_flash_ring_step=dict(batch=1, seq=128, heads=2, head_dim=64),
    kernel_flash_backward=dict(batch=1, seq=256, heads=2, head_dim=64),
    kernel_flash_gqa128=dict(batch=1, seq=256, heads=4, kv_heads=2,
                             head_dim=128),
    kernel_flash_gqa256=dict(batch=1, seq=256, heads=8, kv_heads=1,
                             head_dim=256),
    kernel_flash_window=dict(batch=1, seq=512, heads=4, kv_heads=2,
                             head_dim=128, window=128),
    kernel_flash_block_diffusion=dict(batch=1, seq=128, heads=4, kv_heads=2,
                                      head_dim=128, block=4),
    kernel_flash_grad_block=dict(batch=1, seq=256, heads=2, head_dim=64),
    kernel_conv_bn_relu=dict(batch=2, hw=8, cin=128, cout=128),
    kernel_conv_bn_train=dict(batch=2, hw=8, cin=128, cout=128),
    kernel_gdn_inverse=dict(matrices=130, chunk=16),
    kernel_gdn_chunk=dict(seq=128, key_heads=1, value_heads=2, head_dim=128),
    kernel_ssd_chunk=dict(seq=256, heads=8, head_dim=64, state=128,
                          chunk=128),
    kernel_rope=dict(batch=2, seq=32, heads=4, head_dim=64),
    kernel_moe_sum_rows=dict(tokens=1024, picks=3, width=128, routed=16,
                             held=4),
    kernel_fused_adam=dict(shape=(2, 64, 128)),
    kernel_fused_sgd=dict(shape=(3, 3, 16, 128)),
    kernel_quant_int8=dict(size=1 << 14, block=256),
    kernel_quant_int4=dict(size=1 << 14, block=256))


def test_resnet_phase(cs, mesh4):
    losses = cs.phase_resnet(mesh4, per_chip_batch=2, image_size=32,
                             depth=26, num_classes=10)
    assert losses[-1] < losses[0]


def test_kernel_phase_covers_every_pallas_call(cs):
    cs.phase_kernels(**TOY_KERNELS)
    assert {k.__name__ for k in cs.KERNELS} == set(TOY_KERNELS)
    # every module that holds a pallas_call is reached by some check
    src = open(os.path.join(REPO, "chip_smoke.py")).read()
    for module in ("pallas_kernels", "conv_fused", "optim_kernels",
                   "quant import kernels"):
        assert module in src
