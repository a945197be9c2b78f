"""Elastic end-to-end: real `hvdtrun --elastic` runs driven by a scripted
discovery schedule (ref: test/integration/test_elastic_torch.py +
elastic_common.py — hosts appear/disappear on a timeline; training must
continue from the last commit on the new world, rescale the LR, and
recover within a bounded time).

Log-line contract (tests/data/elastic_main.py):
    rank size batch lr_milli ts_ms
"""

import os
import stat
import subprocess
import sys
import time

import pytest

# Both tests start a real launcher and two generations of jax workers: 16
# and 19 s alone, and process start-up all of it, which stretched past
# 170 s beside six busy xdist workers.  ``slow``: the compose
# test-integration service runs them.  Tier-1 keeps the re-rendezvous on a
# changed host set (tests/test_runner.py TestElasticDriver), state kept
# across a hosts update (tests/test_elastic.py TestRunLoop) and one real
# launcher run that resizes and resumes from a commit
# (tests/test_goodput.py test_kill_rank1_recovers_from_peer_ram_within_budget).
pytestmark = [pytest.mark.integration, pytest.mark.slow]


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BASE_LR_MILLI = 100     # elastic_main.BASE_LR * 1000


def _write_discovery(tmp_path, control_file, before: str, after: str):
    """Discovery script: ``before`` until the control file appears, then
    ``after`` (the scripted schedule, ref elastic_common.py)."""
    path = os.path.join(tmp_path, "discover.sh")
    with open(path, "w") as f:
        f.write(f"""#!/bin/sh
if [ -f {control_file} ]; then
  echo "{after}"
else
  echo "{before}"
fi
""")
    os.chmod(path, os.stat(path).st_mode | stat.S_IEXEC)
    return path


def _launch(spawn, tmp_path, discover, min_np, max_np, coordinator_port,
            batches=30, sleep=0.25):
    log_path = os.path.join(tmp_path, "progress.log")
    state_path = os.path.join(tmp_path, "state.pkl")
    env = dict(os.environ)
    env.update({
        "ELASTIC_TEST_LOG": log_path,
        "ELASTIC_TEST_STATE": state_path,
        "ELASTIC_TEST_BATCHES": str(batches),
        "ELASTIC_TEST_SLEEP": str(sleep),
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",
    })
    proc = spawn(
        [sys.executable, "-m", "horovod_tpu.runner.launch",
         "--min-np", str(min_np), "--max-np", str(max_np),
         "--host-discovery-script", discover,
         "--coordinator-port", str(coordinator_port),
         "--", sys.executable, os.path.join(REPO, "tests", "data",
                                            "elastic_main.py")],
        env=env, cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    return proc, log_path


def _rows(path):
    out = []
    with open(path) as f:
        for ln in f:
            if ln.strip():
                r, s, b, lr, ts = map(int, ln.split())
                out.append((r, s, b, lr, ts))
    return out


def _wait_for_progress(log_path, min_lines, timeout=100, stall=60):
    """Wait for ``min_lines`` rows.  ``stall`` ends the wait early when
    the row count has not moved at all for that long (workers that die
    before their first line).  With ``_finish`` this is all a test may
    wait: 100 + 100 s, under conftest's TEST_TIME_LIMIT_S."""
    deadline = time.monotonic() + timeout
    last_n, last_change = -1, time.monotonic()
    while time.monotonic() < deadline:
        n = len(_rows(log_path)) if os.path.exists(log_path) else 0
        if n >= min_lines:
            return
        if n != last_n:
            last_n, last_change = n, time.monotonic()
        elif time.monotonic() - last_change > stall:
            break
        time.sleep(0.2)
    pytest.fail("phase made no progress")


def _finish(proc, timeout=100):
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
        pytest.fail(f"elastic run hung:\n{out.decode()[-3000:]}")
    assert proc.returncode == 0, out.decode()[-3000:]
    return out


def _recovery_ms(rows, old_size, new_size):
    """ms between the last old-world log line and the first new-world
    one — the full process-restart + re-init + re-jit recovery cost of
    the TPU elastic model (documented: restart-based, SURVEY §5.3)."""
    last_old = max(ts for _, s, _, _, ts in rows if s == old_size)
    first_new = min(ts for _, s, _, _, ts in rows if s == new_size)
    return first_new - last_old


def test_elastic_scale_up_mid_training(tmp_path, spawn):
    control = os.path.join(tmp_path, "scale_up_now")
    discover = _write_discovery(tmp_path, control,
                                before="localhost:1", after="localhost:2")
    proc, log_path = _launch(spawn, tmp_path, discover, 1, 2, 29731)

    _wait_for_progress(log_path, 6)
    open(control, "w").write("go")
    _finish(proc)

    rows = _rows(log_path)
    sizes = {s for _, s, _, _, _ in rows}
    assert sizes == {1, 2}, f"expected a 1->2 transition, saw sizes {sizes}"
    # Progress continuity: the 2-world resumes from a committed point.
    first_two_world_batch = next(b for _, s, b, _, _ in rows if s == 2)
    assert first_two_world_batch > 1, "scale-up restarted from scratch"
    assert max(b for _, _, b, _, _ in rows) == 30
    # Both ranks of the new world logged.
    assert {r for r, s, _, _, _ in rows if s == 2} == {0, 1}
    # LR rescale on resize: base*1 before, base*2 after (linear scaling).
    assert {lr for _, s, _, lr, _ in rows if s == 1} == {BASE_LR_MILLI}
    assert {lr for _, s, _, lr, _ in rows if s == 2} == {2 * BASE_LR_MILLI}
    # Bounded recovery: restart + re-init + re-jit (measured ~2-5s on an
    # idle box; the generous bound absorbs single-core CI contention when
    # the whole suite runs concurrently).
    rec = _recovery_ms(rows, 1, 2)
    print(f"scale-up recovery (restart+reinit+rejit): {rec} ms")
    assert 0 <= rec < 150_000, f"recovery took {rec} ms"


def test_elastic_scale_down_mid_training(tmp_path, spawn):
    """Host removed from the discovery schedule: the reference's
    shrink path (ref: elastic/driver.py host-removal -> restart) — the
    remaining world resumes from the last commit with the LR rescaled
    back down."""
    control = os.path.join(tmp_path, "scale_down_now")
    discover = _write_discovery(tmp_path, control,
                                before="localhost:2", after="localhost:1")
    proc, log_path = _launch(spawn, tmp_path, discover, 1, 2, 29741)

    # >= 10 lines from 2 ranks == batch >= 5: safely past the first
    # commit, so the resume-from-commit assertion cannot race the flip.
    _wait_for_progress(log_path, 12)
    open(control, "w").write("go")
    _finish(proc)

    rows = _rows(log_path)
    sizes = {s for _, s, _, _, _ in rows}
    assert sizes == {2, 1}, f"expected a 2->1 transition, saw sizes {sizes}"
    # The shrunk world resumes from a committed batch, not from scratch,
    # and completes the target.
    first_one_world_batch = next(b for _, s, b, _, _ in rows if s == 1)
    assert first_one_world_batch > 1, "scale-down restarted from scratch"
    assert max(b for _, _, b, _, _ in rows) == 30
    # Only rank 0 remains in the shrunk world.
    assert {r for r, s, _, _, _ in rows if s == 1} == {0}
    # LR rescales back down with the world.
    assert {lr for _, s, _, lr, _ in rows if s == 2} == {2 * BASE_LR_MILLI}
    assert {lr for _, s, _, lr, _ in rows if s == 1} == {BASE_LR_MILLI}
    rec = _recovery_ms(rows, 2, 1)
    print(f"scale-down recovery (restart+reinit+rejit): {rec} ms")
    assert 0 <= rec < 90_000, f"recovery took {rec} ms"
