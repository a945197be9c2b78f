"""chip_smoke.py's phase functions at toy size on the CPU simulator — the
rehearsal of what the script does at full width on the chip — and the
proof that the script itself has no way to pass without one.  The kernel
and ResNet phases: tests/test_chip_smoke_kernels.py."""

import importlib.util
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOY_LM = dict(layers=2, d_model=64, heads=4, d_ff=128, vocab=512,
              loss_chunk=128)


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def mesh4(hvd, devices):
    """Four 'chips', as on the four-chip host."""
    return Mesh(np.asarray(devices[:4], dtype=object), ("dp",))


def test_lm_phase_runs_the_frameworks_exchange_on_every_device(cs, mesh4):
    seen = {}

    def inspect(compiled, params, tokens):
        cs.assert_sharded_over(tokens, 4)
        cs.check_exchange("toy lm", compiled, params, 4)
        seen["allreduces"] = cs.hlo_allreduces(compiled.as_text())

    losses = cs.phase_lm(mesh4, seq=32, per_chip_batch=2, model=TOY_LM,
                         inspect=inspect)
    assert len(losses) == 4 and losses[-1] < losses[0]
    assert seen["allreduces"] and all(g == 4 for _, g in seen["allreduces"])


def test_long_seq_phase_compares_the_kernel_with_xla_attention(
        cs, mesh4, monkeypatch):
    # On the chip `auto` selects the kernel at seq 4096 x 8 per chip; on
    # the CPU nothing does, so the toy run forces it (interpret mode).
    monkeypatch.setenv("HVDT_FLASH_ATTENTION", "on")
    fwd = cs.phase_long_seq(mesh4, seq=128, per_chip_batch=1, model=TOY_LM)
    assert abs(fwd["on"] - fwd["off"]) < 2e-2 * abs(fwd["off"])


def test_long_seq_phase_fails_when_the_kernel_is_not_selected(
        cs, mesh4, monkeypatch):
    monkeypatch.delenv("HVDT_FLASH_ATTENTION", raising=False)
    with pytest.raises(AssertionError, match="not selected"):
        cs.phase_long_seq(mesh4, seq=128, per_chip_batch=1, model=TOY_LM)


def test_dp4_training_matches_one_device(cs, hvd, devices):
    runs = cs.phase_dp_matches_single(
        devices[:4], seq=32, global_batch=8, model=TOY_LM,
        dtype=jnp.float32)
    assert set(runs) == {"dp", "one"}


def test_loss_check_rejects_nan_and_rising(cs):
    cs.check_losses("ok", [3.0, 2.5, 2.0])
    with pytest.raises(AssertionError, match="non-finite"):
        cs.check_losses("nan", [3.0, float("nan"), 2.0])
    with pytest.raises(AssertionError, match="did not fall"):
        cs.check_losses("up", [3.0, 3.5, 3.2])


def test_hlo_allreduce_parser(cs):
    hlo = """
  %ar.1 = f32[1024,4096]{1,0} all-reduce(f32[1024,4096]{1,0} %p), channel_id=1, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%add
  %ars = (f32[256]{0}, bf16[8,2]{1,0}) all-reduce-start((f32[256]{0}, bf16[8,2]{1,0}) %t), replica_groups=[1,4]<=[4], to_apply=%add
  %ard = (f32[256]{0}, bf16[8,2]{1,0}) all-reduce-done(%ars)
  %pair = f32[] all-reduce(f32[] %x), replica_groups={{0,1},{2,3}}, to_apply=%add
  %other = f32[4]{0} add(f32[4]{0} %a, f32[4]{0} %b)
"""
    assert cs.hlo_allreduces(hlo) == [
        (1024 * 4096 * 4, 4), (256 * 4 + 16 * 2, 4), (4, 2)]


def test_script_exits_nonzero_without_a_tpu_having_run_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], env=env,
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert proc.returncode != 0
    assert "platform: cpu" in proc.stdout
    assert '"ok"' not in proc.stdout and "[lm" not in proc.stdout
    assert "no TPU" in proc.stderr
