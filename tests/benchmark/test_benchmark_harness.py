"""The family builders against the plain references, and one run of each
kind of cell through ``harness.run_cell``, at toy size on the CPU
simulator (the first two rehearsals of benchmark/README.md); and the proof
that ``run.py`` prints no result without a TPU."""

import json
import math
import os
import subprocess
import sys
import time

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, manifest  # noqa: E402

TOY = {
    "transformer_lm": (
        dict(layers=2, d_model=64, heads=4, kv_heads=4, d_ff=128, vocab=512,
             loss_chunk=128),
        dict(seq=64, per_chip_batch=2)),
    "resnet": (
        # 8 images of 32x32 through batch norm in bf16 are far noisier than
        # the full size; the float32 test below is the one that is tight.
        # At the full size's learning rate the toy's loss jumps about.
        dict(depth=26, num_classes=10, image_size=32,
             optimizer=dict(name="sgd", learning_rate=0.002, momentum=0.9),
             tolerances=dict(
            loss_rel=0.1, grad_norm_rel=0.2, leaf_cosine_min={
                "conv_stem": 0.5, "s1b0/conv2": 0.5, "s3b0/conv3": 0.5,
                "fc_w": 0.9, "s0b0/bn1/scale": 0.5})),
        dict(per_chip_batch=16)),
}


def toy_cell(name, **config_changes):
    cell = manifest.load_cell(name)
    config, traffic = TOY[cell["config_data"]["family"]]
    cell["config_data"] = {**cell["config_data"], **config,
                           **config_changes}
    cell["traffic"] = dict(cell["traffic"], **traffic)
    return cell


def toy_family(name, **config_changes):
    cell = toy_cell(name, **config_changes)
    return manifest.load_family(cell["config_data"]["family"]).build(
        cell["config_data"], cell["traffic"])


@pytest.fixture()
def v5e_peaks(monkeypatch):
    """The CPU is in no table of peaks; the toy runs borrow the v5e's."""
    real = manifest.load_peaks
    monkeypatch.setattr(manifest, "load_peaks",
                        lambda kind: real("TPU v5 lite"))


# In float32 the system and the reference compute the same mathematics:
# they agree to rounding.  That is what shows the reference is the same
# model; the bf16 tolerances of the configuration files are the chip's.
@pytest.mark.parametrize("cell", ["lm24x1024_s512_b128",
                                  "lm24x1024_s4096_b8", "resnet50_train"])
def test_family_and_reference_agree_in_float32(cell):
    family = toy_family(cell, compute_dtype="float32")
    params = jax.jit(family.init)(jax.random.PRNGKey(0))
    got = harness.reference_check(family, params, jax.random.PRNGKey(1),
                                  jax.devices()[0], mosaic=False)
    assert got["loss_rel"] < 1e-5
    assert got["grad_norm_rel"] < 1e-4
    assert min(got["leaf_cosine"].values()) > 0.9999
    assert got["ok"]


def test_resnet_at_full_depth_agrees_with_the_reference_in_float32():
    # Depth 50 has the blocks with an identity shortcut that ResNet-26
    # lacks, and the leaves the configuration file names.  Fifty layers
    # of batch norm amplify even float32 rounding (0.998 at 64x64).
    full = manifest.load_cell("resnet50_train")["config_data"]
    family = toy_family("resnet50_train", compute_dtype="float32", depth=50,
                        tolerances=full["tolerances"])
    params = jax.jit(family.init)(jax.random.PRNGKey(0))
    got = harness.reference_check(family, params, jax.random.PRNGKey(1),
                                  jax.devices()[0], mosaic=False)
    assert got["loss_rel"] < 1e-4 and got["grad_norm_rel"] < 1e-2
    assert set(got["leaf_cosine"]) == set(
        full["tolerances"]["leaf_cosine_min"])
    assert min(got["leaf_cosine"].values()) > 0.99


def test_lm_at_bf16_stays_inside_the_configurations_tolerances():
    family = toy_family("lm24x1024_s512_b128")
    params = jax.jit(family.init)(jax.random.PRNGKey(0))
    got = harness.reference_check(family, params, jax.random.PRNGKey(1),
                                  jax.devices()[0], mosaic=False)
    assert got["ok"], got
    assert got["loss_rel"] > 0          # bf16 did run


def test_reference_check_fails_a_wrong_model():
    family = toy_family("lm24x1024_s512_b128", compute_dtype="float32")
    params = jax.jit(family.init)(jax.random.PRNGKey(0))
    import dataclasses
    wrong = dataclasses.replace(
        family, reference_loss=lambda p, t: family.reference_loss(
            dict(p, ln_f=p["ln_f"] * 1.05), t))
    got = harness.reference_check(wrong, params, jax.random.PRNGKey(1),
                                  jax.devices()[0], mosaic=False)
    assert not got["ok"]


def test_the_flash_path_is_pinned_by_what_the_step_was_seen_to_do():
    family = toy_family("lm24x1024_s4096_b8")
    assert family.sample_env(True) == {"HVDT_FLASH_ATTENTION": "on"}
    assert family.sample_env(False) == {"HVDT_FLASH_ATTENTION": "off"}
    assert family.sample_size == 1      # one sequence at 4096, see the cell


@pytest.mark.parametrize("cell, unit", [
    ("lm24x1024_s512_b128", "tokens_per_s_chip"),
    ("resnet50_train", "images_per_s_chip"),
    ("lm24x1024_s512_dp4", "tokens_per_s_chip")])
def test_run_cell_at_toy_size(hvd, devices, v5e_peaks, cell, unit, tmp_path):
    toy = toy_cell(cell)
    # Long enough for the eight steps the falling-loss check wants, also
    # on a loaded machine (the toy ResNet takes 80 ms a step on an idle one).
    seconds = 4.0 if cell == "resnet50_train" else 1.0
    result = harness.run_cell(
        toy, devices, seed=3, seconds=seconds, trace=False,
        started_at=time.perf_counter(), trace_dir=str(tmp_path))
    window = result["checks"]["window"]
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 8
    assert window["steps"] == result["attempted"]
    assert window["elapsed_s"] >= seconds
    assert window["compiles_in_window"] == 0
    assert set(result["metrics"]) == {unit, "peak_hbm_gib", "setup_s"}
    assert result["metrics"][unit]["unit"] == unit.replace("_per_s_chip",
                                                           "/s/chip")
    per_step = toy["traffic"]["per_chip_batch"] * toy["traffic"].get("seq", 1)
    assert result["metrics"][unit]["value"] == pytest.approx(
        result["attempted"] * per_step / window["elapsed_s"])
    assert all(math.isfinite(m["value"]) and m["value"] > 0
               for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"
    # What the reference check found on the device and what it left (the
    # CPU keeps no such account: the readings are there and empty).
    assert set(result["checks"]["reference"]["device_bytes"]) == {
        "before", "after"}
    if cell.endswith("dp4"):
        assert result["checks"]["dp"]["ok"]
        assert len(result["checks"]["dp"]["dp"]) == 4
    json.dumps(result)                  # the line is serialisable


def test_the_fullest_devices_bytes_are_the_ones_recorded():
    class Device:
        def __init__(self, **stats):
            self.stats = stats or None

        def memory_stats(self):
            return self.stats

    full = dict(bytes_in_use=6, bytes_reserved=5, peak_bytes_in_use=9,
                bytes_limit=16)
    devices = [Device(bytes_in_use=7, bytes_reserved=1, peak_bytes_in_use=8),
               Device(**full), Device()]
    assert harness.fullest_device_bytes(devices) == {
        "bytes_in_use": 6, "bytes_reserved": 5, "peak_bytes_in_use": 9}
    assert harness.fullest_device_bytes([Device()]) == {}


def test_run_window_keeps_the_host_two_steps_ahead_at_most():
    class Fake:
        def __init__(self):
            self.dispatched, self.fetched, self.lead = 0, 0, 0

        def step(self, batch):
            self.dispatched += 1
            outer = self

            class Loss:
                def __float__(self):
                    outer.fetched += 1
                    return 1.0
            self.lead = max(self.lead, self.dispatched - self.fetched)
            return Loss()

    fake = Fake()
    losses, elapsed, started, raised = harness.run_window(
        fake, [()], steps=10)
    assert (len(losses), started, raised) == (10, 10, 0)
    assert fake.lead <= 3       # two in flight + the one being dispatched


def _run_py(cwd, env_changes, *args):
    env = dict(os.environ, **env_changes)
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_py_prints_no_result_without_a_tpu():
    r = _run_py(REPO, {"JAX_PLATFORMS": "cpu"},
                "--workload", "resnet50_train", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert "{" not in r.stdout


def test_run_py_fails_without_the_program_beside_it(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_py(str(tmp_path), {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""},
                "--workload", "resnet50_train", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert r.returncode != 0 and "{" not in r.stdout
