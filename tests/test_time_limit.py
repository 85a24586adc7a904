"""The suite's per-test time limit (``test_time_limit`` in pytest.ini, armed
in tests/conftest.py): a test that hangs fails alone and the run goes on to
its end, with the hung test's stack in the log."""

import os
import shutil
import subprocess
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))

HANGS = '''
import time


def test_sleeps_past_the_limit():
    time.sleep(120)


def test_passes():
    pass
'''


def test_a_hung_test_fails_alone_and_the_run_goes_on(tmp_path):
    # the suite's own conftest and ini: the limit under test is the real one
    shutil.copy(os.path.join(TESTS, "conftest.py"), tmp_path)
    shutil.copy(os.path.join(TESTS, os.pardir, "pytest.ini"), tmp_path)
    (tmp_path / "test_hangs.py").write_text(HANGS)
    run = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "xdist", "-n", "2", "-o", "test_time_limit=2",
         "test_hangs.py"],
        cwd=tmp_path, capture_output=True, text=True, timeout=240)
    assert "1 failed, 1 passed" in run.stdout, run.stdout + run.stderr
    assert ("crashed while running 'test_hangs.py::"
            "test_sleeps_past_the_limit'") in run.stdout
    # faulthandler's dump: every thread's stack, the sleeper's frame in it
    assert "Timeout (0:00:02)!" in run.stderr
    assert "in test_sleeps_past_the_limit" in run.stderr
