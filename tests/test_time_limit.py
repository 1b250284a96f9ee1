"""The per-test time limits in conftest.py: a test whose call or set-up
(twice the call's limit) runs past its limit fails with the hook's
message instead of holding its worker, and one deaf to the alarm is ended
at twice the limit."""

import os
import subprocess
import sys
import textwrap

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


def _run_inner(tmp_path, source, **limits):
    (tmp_path / "test_inner.py").write_text(textwrap.dedent(source))
    env = dict(
        os.environ,
        **limits,
        PYTHONPATH=os.pathsep.join(
            [TESTS_DIR, os.environ.get("PYTHONPATH", "")]
        ),
    )
    # as tier-1 runs it: the test runs in an xdist worker
    proc = subprocess.run(
        [
            sys.executable, "-m", "pytest", "-p", "conftest",
            "-p", "xdist", "-n", "1", "-p", "no:cacheprovider",
            "-p", "no:randomly", str(tmp_path / "test_inner.py"),
        ],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=50,
    )
    return proc.returncode, proc.stdout + proc.stderr


def test_sleeping_test_fails_at_its_time_limit(tmp_path):
    rc, out = _run_inner(
        tmp_path,
        """
        import time

        def test_sleeps_past_the_limit():
            time.sleep(60)
        """,
        LIGHTHOUSE_TPU_TEST_TIMEOUT_S="2",
    )
    assert rc == 1, out
    assert "1 failed" in out, out
    assert "call ran past its 2 s time limit" in out, out
    assert "in test_sleeps_past_the_limit" in out, out  # the stack dump


def test_sleeping_fixture_fails_at_its_set_up_limit(tmp_path):
    rc, out = _run_inner(
        tmp_path,
        """
        import time

        import pytest

        @pytest.fixture
        def slow_fixture():
            time.sleep(60)

        def test_needs_the_fixture(slow_fixture):
            pass
        """,
        LIGHTHOUSE_TPU_TEST_TIMEOUT_S="1",
    )
    assert rc == 1, out
    assert "1 error" in out, out
    assert "set-up ran past its 2 s time limit" in out, out


def test_test_deaf_to_the_alarm_is_ended_at_twice_its_limit(tmp_path):
    # SIGALRM blocked stands in for a call stuck inside C++
    rc, out = _run_inner(
        tmp_path,
        """
        import signal
        import time

        def test_blocks_the_alarm():
            signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
            time.sleep(60)
        """,
        LIGHTHOUSE_TPU_TEST_TIMEOUT_S="1",
    )
    assert rc == 1, out
    assert "1 failed" in out, out
    assert "crashed while running" in out, out
    assert "Timeout (0:00:02)!" in out, out
