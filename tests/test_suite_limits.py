"""The suite's own limits (``tests/conftest.py``): a test that waits fails
alone, with every thread's stack, and the tests after it run; no test
gives a child process longer than a test may take itself; and
``ci/tier1_times.py`` tells the file and the case that set a run's wall
time."""

import ast
import glob
import importlib.util
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from conftest import TEST_LIMIT_S, time_limit

TESTS = os.path.dirname(os.path.abspath(__file__))


def _waits_on(event):
    event.wait(60)


def test_the_limit_fails_a_sleeper_with_every_threads_stack():
    """Called with a limit of 0.2 s on a sleep of 30: the failure comes at
    the limit, names this frame and the other thread's, and the handler
    and the timer that were there (this test's own limit) are back."""
    handler = signal.getsignal(signal.SIGALRM)
    left = signal.getitimer(signal.ITIMER_REAL)[0]
    done = threading.Event()
    other = threading.Thread(target=_waits_on, args=(done,))
    other.start()
    t0 = time.monotonic()
    try:
        with pytest.raises(pytest.fail.Exception) as failure:
            with time_limit(0.2):
                time.sleep(30)
    finally:
        done.set()
        other.join(60)
    assert time.monotonic() - t0 < 10
    said = str(failure.value)
    assert "still running after 0.2 s" in said
    assert "_waits_on" in said and "most recent call first" in said
    assert "test_the_limit_fails_a_sleeper_with_every_threads_stack" in said
    assert signal.getsignal(signal.SIGALRM) is handler
    assert 0 < signal.getitimer(signal.ITIMER_REAL)[0] <= left <= TEST_LIMIT_S


def test_the_limit_leaves_a_test_that_ends_alone():
    handler = signal.getsignal(signal.SIGALRM)
    with time_limit(0.2):
        pass
    time.sleep(0.4)     # an alarm left behind would fail the test here
    assert signal.getsignal(signal.SIGALRM) is handler


def test_a_sleeping_test_fails_alone_and_the_file_goes_on(tmp_path):
    """The hook as the suite has it, with the constant set to 0.5 s: of a
    file's three tests the sleeper fails, with the stacks, and the tests
    before and after it pass."""
    (tmp_path / "conftest.py").write_text(
        "import importlib.util\n"
        "spec = importlib.util.spec_from_file_location(\n"
        "    'suite_conftest', %r)\n"
        "suite = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(suite)\n"
        "suite.TEST_LIMIT_S = 0.5\n"
        "pytest_runtest_call = suite.pytest_runtest_call\n"
        % os.path.join(TESTS, "conftest.py"))
    (tmp_path / "test_three.py").write_text(
        "import time\n"
        "def test_before(): pass\n"
        "def test_sleeps(): time.sleep(60)\n"
        "def test_after(): pass\n")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", str(tmp_path), "-q",
         "-p", "no:cacheprovider", "--rootdir", str(tmp_path)],
        capture_output=True, text=True, timeout=120, cwd=str(tmp_path))
    assert proc.returncode == 1, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "1 failed, 2 passed" in proc.stdout, proc.stdout[-2000:]
    assert "still running after 0.5 s" in proc.stdout
    assert "in test_sleeps" in proc.stdout


#: calls that start or wait for a child process
_WAITS = ("subprocess.run", "subprocess.check_output",
          "subprocess.check_call", "subprocess.call")


def _defaults(fn):
    """``{parameter: its default's node}`` of a function definition."""
    args = fn.args
    named = args.posonlyargs + args.args
    found = dict(zip((a.arg for a in named[::-1]), args.defaults[::-1]))
    found.update((a.arg, d) for a, d in
                 zip(args.kwonlyargs, args.kw_defaults) if d is not None)
    return found


def _child_waits(path):
    """``(line, call, timeout)`` of every call in ``path`` that waits for a
    child: ``subprocess``'s own, any ``.communicate()``, and the helper
    the examples' tests call ``_run``.  ``timeout`` is the constant
    passed; where a helper hands its own parameter on, or is called
    without one, the parameter's default; None where there is none."""
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    helper = {node.name: _defaults(node).get("timeout")
              for node in tree.body if isinstance(node, ast.FunctionDef)}
    found = []

    def visit(node, defaults):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defaults = _defaults(node)
        if isinstance(node, ast.Call):
            name = ast.unparse(node.func)
            if name in _WAITS or name.endswith(".communicate") \
                    or name == "_run":
                given = {k.arg: k.value for k in node.keywords}.get(
                    "timeout", helper.get(name))
                if isinstance(given, ast.Name):
                    given = defaults.get(given.id)
                found.append((node.lineno, name,
                              given.value if isinstance(given, ast.Constant)
                              else None))
        for child in ast.iter_child_nodes(node):
            visit(child, defaults)

    visit(tree, {})
    return found


def test_no_child_process_is_given_longer_than_a_test():
    """Every wait for a child in ``tests/*.py`` passes a ``timeout``, a
    constant of at most ``TEST_LIMIT_S``: a child that hangs ends its own
    test with its output, before the test's limit ends it without."""
    waits = late = 0
    for path in sorted(glob.glob(os.path.join(TESTS, "*.py"))):
        for line, call, timeout in _child_waits(path):
            waits += 1
            if timeout is None or timeout > TEST_LIMIT_S:
                late += 1
                print("%s:%d: %s(timeout=%r)"
                      % (os.path.basename(path), line, call, timeout))
    assert waits > 60, "the scan found %d waits: it reads too little" % waits
    assert late == 0, "%d waits without a timeout of at most %d s" \
        % (late, TEST_LIMIT_S)


def test_the_scan_tells_a_wait_without_a_timeout(tmp_path):
    path = tmp_path / "t.py"
    path.write_text(
        "import subprocess\n"
        "def _run(*args, timeout=420):\n"
        "    return subprocess.run(args, timeout=timeout)\n"
        "def test_a():\n"
        "    _run('x')\n"
        "    _run('x', timeout=30)\n"
        "    subprocess.run(['x'])\n"
        "    subprocess.Popen(['x']).communicate(timeout=5)\n"
        "    subprocess.check_output(['x'], timeout=600)\n")
    assert _child_waits(str(path)) == [
        (3, "subprocess.run", 420), (5, "_run", 420), (6, "_run", 30),
        (7, "subprocess.run", None),
        (8, "subprocess.Popen(['x']).communicate", 5),
        (9, "subprocess.check_output", 600)]


# -- ci/tier1_times.py -----------------------------------------------------------
def _tier1_times():
    spec = importlib.util.spec_from_file_location(
        "tier1_times", os.path.join(os.path.dirname(TESTS), "ci",
                                    "tier1_times.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _junit(path, cases):
    path.write_text(
        '<?xml version="1.0"?><testsuites><testsuite name="pytest">%s'
        "</testsuite></testsuites>" % "".join(
            '<testcase classname="%s" name="%s" time="%s"/>' % case
            for case in cases))
    return str(path)


def test_tier1_times_names_the_file_and_the_case_over_their_share(tmp_path,
                                                                  capsys):
    """120 test-seconds on two workers: a share of 60 s, so a file may
    take 36 s and a case 9.  One file of 40 s breaks the first rule, its
    case of 11 s the second, and the run without them breaks neither."""
    times = _tier1_times()
    even = [("tests.test_%d" % f, "test_%d" % c, "4.0")
            for f in range(4) for c in range(5)]
    heavy = [("tests.test_heavy", "test_%d[a-b]" % c, "5.8")
             for c in range(5)] + [("tests.test_heavy", "test_long", "11.0")]
    assert times.main(["", _junit(tmp_path / "even.xml", even), "2"]) == 0
    assert "broken" not in capsys.readouterr().out
    assert times.main(["", _junit(tmp_path / "heavy.xml", even + heavy),
                       "2"]) == 1
    out = capsys.readouterr().out
    assert "26 cases, 120.0 test-seconds; a worker's share of 2: 60.0 s; " \
        "a file may take 36.0 s, a case 9.0 s" in out
    assert "| `tests/test_heavy.py` | 6 | 40.0 | `test_long` 11.0 |" in out
    assert "1 cases over 10 s, 11.0 s of the 120.0" in out
    assert out.split("broken:\n")[1].splitlines() == [
        "file tests/test_heavy.py: 40.0 s, over 36.0",
        "case tests/test_heavy.py::test_long: 11.0 s, over 9.0"]
    assert times.main(["", "a", "b", "c"]) == 2
