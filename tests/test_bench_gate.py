"""``python bench.py`` prints a device metric from the device or not at
all.

The parent makes ONE attempt in a child process.  A child that fails,
times out, or ran anywhere but on a TPU (with ``JAX_PLATFORMS`` unset a
failed TPU init falls back to the CPU with only a warning) means: no
metric line on stdout, non-zero exit.  Nothing is cached, retried, or
promoted.  The direct ``--_child`` form under ``JAX_PLATFORMS=cpu`` stays
a way to drive a code path and says ``"platform": "cpu"``.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _line(platform):
    return json.dumps({
        "metric": "resnet50_images_per_sec_per_chip", "value": 2693.7,
        "unit": "images/sec/chip", "platform": platform,
        "device_kind": "TPU v5 lite" if platform == "tpu" else platform,
        "batch_size": 128})


@pytest.fixture()
def bench(monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_main(bench, monkeypatch, capsys, argv, child):
    """main() with the child process replaced by ``child(cmd) ->
    (returncode, stdout)`` or an exception to raise."""
    calls = []

    def fake_run(cmd, **kw):
        calls.append(cmd)
        out = child(cmd)
        if isinstance(out, BaseException):
            raise out
        rc, stdout = out
        return subprocess.CompletedProcess(cmd, rc, stdout, "")

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.setattr(sys, "argv", ["bench.py"] + argv)
    rc = bench.main()
    return rc, capsys.readouterr().out, calls


MODES = [[], ["--serve"], ["--serve-llm"]]


@pytest.mark.parametrize("argv", MODES)
def test_tpu_child_line_is_printed_after_one_attempt(
        bench, monkeypatch, capsys, argv):
    rc, out, calls = _run_main(bench, monkeypatch, capsys, argv,
                               lambda cmd: (0, "noise\n" + _line("tpu")))
    assert rc == 0
    assert out.strip() == _line("tpu")
    assert len(calls) == 1
    # the child is told it is a measurement, so it can refuse early
    assert "--_child" in calls[0] and "--_measure" in calls[0]


@pytest.mark.parametrize("argv", MODES)
def test_cpu_number_is_never_printed(bench, monkeypatch, capsys, argv):
    """A child that exited 0 on the CPU (JAX's silent fallback) is a
    failure: nothing from a CPU under a device metric's name."""
    rc, out, calls = _run_main(bench, monkeypatch, capsys, argv,
                               lambda cmd: (0, _line("cpu")))
    assert rc != 0
    assert out == ""
    assert len(calls) == 1


@pytest.mark.parametrize("child", [
    lambda cmd: (1, _line("tpu")),          # crashed after printing
    lambda cmd: (0, "no json here"),
    lambda cmd: subprocess.TimeoutExpired(cmd, 1),
], ids=["nonzero-exit", "no-line", "timeout"])
def test_failed_child_means_no_metric_and_no_retry(
        bench, monkeypatch, capsys, child):
    rc, out, calls = _run_main(bench, monkeypatch, capsys, [], child)
    assert rc != 0
    assert out == ""
    assert len(calls) == 1


def test_no_cached_measurement_mechanism(bench):
    assert not os.path.exists(os.path.join(REPO, ".bench_last_good.json"))
    for name in ("_save_last_good", "_load_last_good", "LAST_GOOD_PATH"):
        assert not hasattr(bench, name)


def test_bench_without_a_chip_exits_nonzero_with_no_metric():
    """The real thing, end to end: parent -> child -> JAX on the CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                          env=env, capture_output=True, text=True,
                          timeout=200)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "not a TPU" in proc.stderr
