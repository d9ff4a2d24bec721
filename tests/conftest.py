"""Test config: force a CPU-only 8-device virtual mesh BEFORE jax initializes.

Mirrors the reference's fake-device fixture strategy (SURVEY §4: multi-device
tests use mx.cpu(0)/mx.cpu(1) contexts without a cluster) — 8 virtual CPU
devices stand in for an 8-chip TPU slice, so sharding/collective paths
compile and run in CI.

The persistent compile cache is on by default for users
(``mxnet_tpu.compile_cache``); the suite keeps it OFF through JAX's own
switch so CPU test programs never land in the checkout's ``.jax_cache``.
Tests that exercise the cache enable it on a ``tmp_path`` themselves, and
the example/worker subprocesses the suite starts inherit the same
environment.
"""

import contextlib
import faulthandler
import os
import signal
import sys
import tempfile
import threading

import pytest

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = \
        flags + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 run (-m 'not slow'); fault-"
        "injection tests must stay fast enough to NOT need this")


#: seconds a test's call may take before it fails alone, with every
#: thread's stack: a test that waits must not take the run's own limit
#: with it.  A constant, not an option; no child process a test starts
#: may be given longer (tests/test_suite_limits.py)
TEST_LIMIT_S = 300


@contextlib.contextmanager
def time_limit(seconds):
    """Fails the test that is still inside the block ``seconds`` later:
    ``SIGALRM`` interrupts the worker's main thread, which is the one
    that runs the tests, where it sleeps, waits on a lock or a child, or
    runs Python (a call into native code is left to return first).  The
    failure carries ``faulthandler``'s dump of every thread.  The handler
    and the timer found are put back, so limits nest."""
    if threading.current_thread() is not threading.main_thread():
        yield       # signal.signal() is the main thread's alone
        return

    def on_alarm(signum, frame):
        with tempfile.TemporaryFile("w+") as dump:
            faulthandler.dump_traceback(file=dump, all_threads=True)
            dump.seek(0)
            pytest.fail("still running after %g s; every thread's stack:\n%s"
                        % (seconds, dump.read()))

    handler = signal.signal(signal.SIGALRM, on_alarm)
    timer = signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, *timer)
        signal.signal(signal.SIGALRM, handler)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    with time_limit(TEST_LIMIT_S):
        return (yield)


def pytest_sessionfinish(session, exitstatus):
    """Dump real op-invocation counts (OpDef.apply calls) when asked:
    MXNET_OP_COVERAGE_OUT=path pytest tests/ ... writes {op: count}.
    tools/gen_op_census.py consumes the dump so the census coverage
    column counts executions, not word-grep mentions."""
    try:
        from mxnet_tpu.test_utils import dump_op_coverage
    except Exception:
        return
    dump_op_coverage("OpDef.apply call counts from one pytest session")
