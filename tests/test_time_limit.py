"""The per-test limit of tests/conftest.py: a test that waits past it is
failed where it waits, a quick one is left alone, and ``spawned`` leaves no
child behind on either way out."""

import os
import subprocess
import sys
import time

import pytest

from conftest import spawned


@pytest.mark.time_limit(0.2)
def test_a_sleeping_test_is_failed_by_the_limit():
    started = time.monotonic()
    with pytest.raises(pytest.fail.Exception, match="limit of 0.2 s"):
        time.sleep(30)
    assert time.monotonic() - started < 5


@pytest.mark.time_limit(0.2)
def test_a_quick_test_is_not():
    pass


def test_and_its_timer_is_disarmed_when_it_ends():
    # the 0.2 s alarm of the test above would fire inside this sleep
    time.sleep(0.5)


def _alive(pid):
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    return True


def _gone_within(pid, seconds):
    """An orphan is a zombie until init reaps it: give it a moment."""
    deadline = time.monotonic() + seconds
    while _alive(pid) and time.monotonic() < deadline:
        time.sleep(0.05)
    return not _alive(pid)


@pytest.mark.parametrize("fails", [False, True], ids=["return", "raise"])
def test_spawned_kills_and_reaps_the_child_and_its_children(fails):
    # a child that starts a grandchild in a session of its own, as the
    # launcher starts its workers, and prints its pid; both sleep
    code = ("import subprocess, sys, time; "
            "g = subprocess.Popen([sys.executable, '-c', "
            "'import time; time.sleep(60)'], start_new_session=True); "
            "print(g.pid, flush=True); time.sleep(60)")
    try:
        with spawned([sys.executable, "-c", code],
                     stdout=subprocess.PIPE) as proc:
            grandchild = int(proc.stdout.readline())
            assert _alive(proc.pid) and _alive(grandchild)
            if fails:
                raise RuntimeError("a failed assert, say")
    except RuntimeError:
        assert fails
    assert proc.returncode is not None          # reaped
    assert _gone_within(grandchild, 5)


@pytest.mark.time_limit(2)
def test_the_limit_ends_a_launch_and_its_workers(tmp_path):
    """``run_static`` joins its workers without a deadline: the limit's
    exception ends the join, and the launcher takes its workers along."""
    from horovod_tpu.runner import launch

    pidfile = tmp_path / "pid"
    args = launch.parse_args(
        ["-np", "1", "--", "sh", "-c", f"echo $$ > {pidfile}; exec sleep 60"])
    with pytest.raises(pytest.fail.Exception, match="limit of 2 s"):
        launch.run_static(args)
    assert _gone_within(int(pidfile.read_text()), 5)
