"""Test harness configuration.

Forces an 8-device virtual CPU mesh (the pattern SURVEY.md §7 prescribes for
testing multi-chip sharding without TPU hardware — analogous to how the
reference tests distributed code with multi-process-on-one-host,
``test_dist_base.py:786``). Must run before jax is imported anywhere.
"""

import contextlib
import faulthandler
import os
import signal
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# DO NOT enable jax's persistent compilation cache here (and do not call
# core.compile_cache.enable_compile_cache from a test).  On jaxlib
# 0.4.37's CPU backend cache-hit executables for the multi-device
# donated train steps were UNSAFE: heap corruption mid-suite and
# silently wrong numerics on reload (test_train_resume trajectories
# diverged).  Not re-tested on jaxlib 0.9.0's CPU backend (ROADMAP A5);
# a crash kills the whole pytest process and every test after it.

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def join_within(threads, seconds, what):
    """Join every thread, or fail saying which of them did not end."""
    deadline = time.monotonic() + seconds
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    stuck = [t.name for t in threads if t.is_alive()]
    assert not stuck, (
        f"{what}: still running after {seconds:g} s: {stuck}")


@pytest.fixture(autouse=True)
def _seeded():
    import paddle_hackathon_tpu as paddle

    np.random.seed(0)
    paddle.seed(0)
    yield


# One limit for every test.  A test that waits on a process, a thread, a
# socket or a queue which never answers used to hold its xdist worker
# until the whole run was killed from outside (exit 124, no junit file, no
# failure named).  Past this many seconds in its set-up, its call or its
# tear-down the test FAILS, by name and with the stack it was waiting in,
# and the worker goes on to the next test.  The slowest tier-1 test took
# 374 s beside a second copy of the suite, 155 s without one (README.md,
# "Running the tests").  ``@pytest.mark.timeout(seconds)`` gives one test
# another limit.
TEST_TIMEOUT_S = 600.0

_real_stderr_fd = None  # fd 2 as it was before any test's capture


@contextlib.contextmanager
def _time_limit(item, phase):
    marker = item.get_closest_marker("timeout")
    limit = float(marker.args[0]) if marker else TEST_TIMEOUT_S

    def _expired(signum, frame):
        # every thread's stack goes to the test's captured stderr: the
        # main thread's is in the failure itself, the others' (a feeder,
        # a server, a tick loop holding what the test waits for) only here
        sys.__stderr__.write(
            f"\n{item.nodeid} ({phase}): past {limit:g} s; all threads:\n")
        faulthandler.dump_traceback(file=sys.__stderr__, all_threads=True)
        # pytest.fail raises a BaseException: a poll loop's
        # ``except Exception: continue`` does not swallow it
        pytest.fail(
            f"{item.nodeid} ({phase}) ran past the per-test limit of "
            f"{limit:g} s (tests/conftest.py TEST_TIMEOUT_S); the "
            "traceback shows where it was waiting", pytrace=True)

    previous = signal.signal(signal.SIGALRM, _expired)
    signal.setitimer(signal.ITIMER_REAL, limit)
    # the handler runs only when the main thread is back in the
    # interpreter.  A test stuck in C code past that gets at least its
    # stacks onto the run's own stderr, from faulthandler's watchdog
    faulthandler.dump_traceback_later(limit + 10, file=_real_stderr_fd)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, previous)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    with _time_limit(item, "setup"):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    with _time_limit(item, "call"):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item):
    with _time_limit(item, "teardown"):
        return (yield)


def pytest_configure(config):
    global _real_stderr_fd
    _real_stderr_fd = os.dup(2)  # capture is not on yet: the run's own
    config.addinivalue_line(
        "markers", "slow: minutes-long engine, trainer and HTTP-server "
        "drills; tier-1 runs -m 'not slow'")
    config.addinivalue_line(
        "markers", "timeout(seconds): this test's own time limit in place "
        "of tests/conftest.py TEST_TIMEOUT_S")
