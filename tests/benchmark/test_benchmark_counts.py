"""The benchmark's operation and byte counts against hand-worked numbers.
CPU only; nothing here touches a device."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import manifest  # noqa: E402
from benchmark.families import resnet, transformer_lm  # noqa: E402
from benchmark.layer_metrics import (allreduce_bytes, conv_roofline,  # noqa: E402
                                     flash_bwd_call_cost,
                                     flash_fwd_call_cost, roofline)


def _config(name):
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


# 24 layers x (4 x 1024^2 projections + 3 x 1024 x 4096 SwiGLU) = 402,653,184
# matmul weights, + 1024 x 30528 tied head = 433,913,856; attention over the
# causal half adds seq/2 x 2 x 1024 per layer per token.  x2 per
# multiply-add, x3 for forward + backward.
@pytest.mark.parametrize("seq, gflop", [(512, 2.679), (4096, 3.207)])
def test_lm_flops_per_token(seq, gflop):
    got = transformer_lm.flops_per_token(_config("lm24x1024"), seq)
    hand = 3 * 2 * (433_913_856 + 24 * (seq // 2) * 2 * 1024)
    assert got == hand
    assert got / 1e9 == pytest.approx(gflop, abs=1e-3)


def test_lm_attention_share_is_a_fifth_at_4096_and_a_fortieth_at_512():
    cfg = _config("lm24x1024")

    def share(seq):
        attn = 3 * 2 * 24 * (seq // 2) * 2 * 1024
        return attn / transformer_lm.flops_per_token(cfg, seq)

    assert share(4096) == pytest.approx(0.188, abs=2e-3)
    assert share(512) == pytest.approx(0.028, abs=2e-3)


def test_resnet50_has_53_convolutions_and_4_09_gmacs_forward():
    cfg = _config("resnet50")
    shapes = resnet.conv_shapes(cfg)
    assert len(shapes) == 53
    macs = sum(kh * kw * cin * cout * out * out
               for kh, kw, cin, cout, out, _ in shapes) + 2048 * 1000
    assert macs == 4_089_184_256            # torchvision's 4.09 GMACs
    stem = 7 * 7 * 3 * 64 * 112 * 112
    # x2 per multiply-add, x3 forward + backward, the stem's input
    # gradient not needed.
    assert resnet.flops_per_image(cfg) == 2 * (3 * macs - stem)


# The seq-4096 cell's calls: b8 h16 L4096 d64, bf16, causal.  A product
# over the causal half of the score square is 8*16*4096*4096*64 = 137.4
# GFLOP; a [B, L, H*D] tensor is 67.1 MB.
@pytest.mark.parametrize("call_cost, products, tensors, gflop, mb, ms", [
    (flash_fwd_call_cost, 2, 4, 275, 268, 1.395),   # s, p.v; q k v | o
    (flash_bwd_call_cost, 5, 7, 687, 470, 3.488),   # s, dp, dv, dq, dk;
])                                                  # q k v dO | dq dk dv
def test_flash_call_cost_at_the_cells_shape(call_cost, products, tensors,
                                            gflop, mb, ms):
    ops, nbytes = call_cost(8, 4096, 16, 64)
    assert ops == products * 8 * 16 * 4096 * 4096 * 64
    assert nbytes == tensors * 8 * 4096 * 1024 * 2
    assert ops / 1e9 == pytest.approx(gflop, abs=0.5)
    assert nbytes / 1e6 == pytest.approx(mb, abs=0.5)
    least, bound = roofline(ops, nbytes, manifest.load_peaks("TPU v5 lite"))
    assert bound == "compute"
    assert least == pytest.approx(ops / 197e12)
    assert 1e3 * least == pytest.approx(ms, abs=1e-3)


def test_conv_roofline_least_time_is_hbm_bound_overall():
    cfg, peaks = _config("resnet50"), manifest.load_peaks("TPU v5 lite")
    least = conv_roofline.least_seconds(cfg, 128, peaks)
    compute_only = (resnet.flops_per_image(cfg) - 3 * 2 * 2048 * 1000
                    ) * 128 / peaks["bf16_flops_per_s"]
    assert least > compute_only         # some convolutions are HBM-bound
    assert least < 2 * compute_only


def test_roofline_says_which_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert roofline(200.0, 10.0, peaks) == (2.0, "compute")
    assert roofline(100.0, 50.0, peaks) == (5.0, "hbm")


HLO = """
  %all-reduce-start.1 = f32[1024,1024]{1,0} all-reduce-start(f32[1024,1024]{1,0} %p), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add
  %all-reduce.2 = (f32[8]{0}, bf16[4,2]{1,0}) all-reduce(%a, %b), channel_id=2, replica_groups=[1,4]<=[4], to_apply=%add
  %all-reduce.3 = f32[16]{0} all-reduce(f32[16]{0} %c), replica_groups={{0,1},{2,3}}, to_apply=%add
  %all-reduce-done.1 = f32[1024,1024]{1,0} all-reduce-done(%all-reduce-start.1)
"""


def test_hlo_allreduces_reads_bytes_and_group_sizes():
    assert allreduce_bytes.hlo_allreduces(HLO) == [
        (4 * 1024 * 1024, 4), (8 * 4 + 8 * 2, 4), (64, 2)]


def test_unknown_device_kind_is_an_error_not_a_default():
    with pytest.raises(manifest.ManifestError, match="not in benchmark/peaks"):
        manifest.load_peaks("TPU v9 imaginary")
    assert manifest.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
