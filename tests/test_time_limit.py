"""The per-test time limit of ``tests/conftest.py``: a test that waits
for ever fails by name, with the stack it waited in, the worker runs the
next test, and the run ends by itself with its summary and its junit
file.  Driven in a subprocess against a temporary test file that loads
this repo's conftest as a plugin."""

import os
import subprocess
import sys
import textwrap
import xml.etree.ElementTree as ET

import pytest

_TESTS = os.path.dirname(os.path.abspath(__file__))

_HANGS = {
    "call": """
        import threading
        import pytest

        @pytest.mark.timeout(2)
        def test_waits_for_ever():
            threading.Event().wait()      # nobody sets it

        def test_after_the_hang():
            pass
        """,
    "setup": """
        import queue
        import pytest

        @pytest.fixture
        def peer_that_never_answers():
            return queue.Queue().get()    # nobody puts

        @pytest.mark.timeout(2)
        def test_waits_for_ever(peer_that_never_answers):
            pass

        def test_after_the_hang():
            pass
        """,
    # the wait swallows Exception, as a poll loop around queue.get does
    "swallowed": """
        import time
        import pytest

        @pytest.mark.timeout(2)
        def test_waits_for_ever():
            while True:
                try:
                    time.sleep(0.05)
                except Exception:
                    continue

        def test_after_the_hang():
            pass
        """,
}


# the source line each kind of hang waits in, and pytest's word for it
# (a failure in a fixture is an "error")
_WAITS = {"call": ("threading.Event().wait()", "failure"),
          "setup": ("queue.Queue().get()", "error"),
          "swallowed": ("time.sleep(0.05)", "failure")}


@pytest.mark.parametrize("xdist", [(), ("-p", "xdist", "-n", "1")],
                         ids=["serial", "xdist"])
def test_a_test_past_its_limit_fails_by_name_and_the_run_goes_on(
        tmp_path, xdist):
    for where, body in _HANGS.items():
        # the package is imported and seeded while the file is collected,
        # so that the two seconds are the wait's and not the first import's
        (tmp_path / f"test_hang_{where}.py").write_text(
            "import paddle_hackathon_tpu as paddle\npaddle.seed(0)\n"
            + textwrap.dedent(body))
    junit = tmp_path / "junit.xml"
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [_TESTS, os.path.dirname(_TESTS), os.environ.get("PYTHONPATH", "")]))
    # 180 s: the inner run imports jax and the package, then waits 3 x 2 s
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "conftest", "-p",
         "no:cacheprovider", "-p", "no:randomly", "--rootdir", str(tmp_path),
         f"--junitxml={junit}", "-v", *xdist, str(tmp_path)],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=180)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 1, out
    assert "2 failed, 3 passed, 1 error" in out, out
    assert "all threads:" in out and "most recent call first" in out, out
    cases = {(c.get("classname"), c.get("name")): c
             for c in ET.parse(junit).getroot().iter("testcase")}
    for where, (wait, kind) in _WAITS.items():
        name = f"test_hang_{where}.py::test_waits_for_ever"
        assert f"{name} ({'setup' if where == 'setup' else 'call'}) ran " \
               "past the per-test limit of 2 s" in out, out
        # the main thread's stack names the wait
        assert wait in out, out
        hung = cases[(f"test_hang_{where}", "test_waits_for_ever")]
        assert hung.find(kind) is not None
        after = cases[(f"test_hang_{where}", "test_after_the_hang")]
        assert len(after) == 0  # no failure, no error, no skip


def test_the_limit_is_armed_while_a_test_of_this_suite_runs():
    """Not only in the subprocess above: this very test runs under the
    alarm, with the suite's constant (no test has a marker of its own)."""
    import signal

    import conftest
    left, interval = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < left <= conftest.TEST_TIMEOUT_S and interval == 0
    assert signal.getsignal(signal.SIGALRM).__name__ == "_expired"
