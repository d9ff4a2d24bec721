"""The pre-existing observability trio — profiler spans/dump, Monitor
pattern matching, log.get_logger formatting — plus the hardened
``profiler_set_state`` trace_dir semantics, the ProgressBar/Speedometer
fixes, and the graftlint ``print``/``env-docs`` passes."""

import json
import logging
import os
import re
import subprocess
import sys

import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(autouse=True)
def _profiler_reset(tmp_path):
    """Profiler stopped, events drained, config restored after each test
    (the module is process-global state)."""
    yield
    profiler._state = profiler.State.STOP
    profiler.profiler_set_config(mode="symbolic",
                                 filename=str(tmp_path / "drain.json"))
    profiler.dump_profile()  # clears accumulated events
    profiler.profiler_set_config()  # defaults: symbolic/profile.json


class _Param:
    def __init__(self, epoch=0, nbatch=0, eval_metric=None):
        self.epoch = epoch
        self.nbatch = nbatch
        self.eval_metric = eval_metric


# -- profiler.span on/off + dump_profile ------------------------------------

def test_span_noop_while_stopped(tmp_path):
    assert not profiler.running()
    profiler.profiler_set_config(filename=str(tmp_path / "p.json"))
    with profiler.span("op", "symbolic") as sp:
        assert not sp._on
        sp.sync(3)  # must pass values through untouched while off
    with open(profiler.dump_profile()) as f:
        assert json.load(f)["traceEvents"] == []


def test_span_mode_gating_and_roundtrip(tmp_path):
    profiler.profiler_set_config(mode="symbolic",
                                 filename=str(tmp_path / "p.json"))
    profiler.profiler_set_state("run")
    with profiler.span("sym_op", "symbolic"):
        pass
    with profiler.span("imp_op", "imperative"):  # filtered by mode
        pass
    profiler.profiler_set_state("stop")
    with open(profiler.dump_profile()) as f:
        events = json.load(f)["traceEvents"]
    names = [e["name"] for e in events]
    assert "sym_op" in names and "imp_op" not in names
    ev = events[names.index("sym_op")]
    assert ev["ph"] == "X" and ev["dur"] >= 0 and "ts" in ev
    # dump drains: a second dump is empty
    with open(profiler.dump_profile()) as f:
        assert json.load(f)["traceEvents"] == []


def test_span_mode_all_records_both(tmp_path):
    profiler.profiler_set_config(mode="all",
                                 filename=str(tmp_path / "p.json"))
    profiler.profiler_set_state("run")
    with profiler.span("a", "symbolic"):
        pass
    with profiler.span("b", "imperative"):
        pass
    profiler.profiler_set_state("stop")
    with open(profiler.dump_profile()) as f:
        names = [e["name"] for e in json.load(f)["traceEvents"]]
    assert set(names) >= {"a", "b"}


# -- profiler_set_state trace_dir hardening ---------------------------------

class _TraceCalls:
    def __init__(self, fail_start=False, fail_stop=False):
        self.starts = 0
        self.stops = 0
        self.fail_start = fail_start
        self.fail_stop = fail_stop

    def start_trace(self, d):
        if self.fail_start:
            raise RuntimeError("no trace backend")
        self.starts += 1

    def stop_trace(self):
        if self.fail_stop:
            raise RuntimeError("trace backend died")
        self.stops += 1


def test_failed_start_trace_keeps_state_stopped(tmp_path, monkeypatch):
    import jax

    profiler.profiler_set_config(filename=str(tmp_path / "p.json"),
                                 trace_dir=str(tmp_path / "tb"))
    monkeypatch.setattr(jax, "profiler", _TraceCalls(fail_start=True))
    with pytest.raises(RuntimeError):
        profiler.profiler_set_state("run")
    # _state must not claim RUN when the trace never started
    assert not profiler.running()


def test_failed_stop_trace_keeps_state_running(tmp_path, monkeypatch):
    import jax

    profiler.profiler_set_config(filename=str(tmp_path / "p.json"),
                                 trace_dir=str(tmp_path / "tb"))
    fake = _TraceCalls()
    monkeypatch.setattr(jax, "profiler", fake)
    profiler.profiler_set_state("run")
    fake.fail_stop = True
    with pytest.raises(RuntimeError):
        profiler.profiler_set_state("stop")
    assert profiler.running()  # still running: stop can be retried
    fake.fail_stop = False
    profiler.profiler_set_state("stop")
    assert not profiler.running() and fake.stops == 1


def test_second_stop_and_run_are_idempotent(tmp_path, monkeypatch):
    import jax

    profiler.profiler_set_config(filename=str(tmp_path / "p.json"),
                                 trace_dir=str(tmp_path / "tb"))
    fake = _TraceCalls()
    monkeypatch.setattr(jax, "profiler", fake)
    profiler.profiler_set_state("run")
    profiler.profiler_set_state("run")    # no second start_trace
    profiler.profiler_set_state("stop")
    profiler.profiler_set_state("stop")   # no unmatched stop_trace
    assert fake.starts == 1 and fake.stops == 1


# -- Monitor pattern matching ------------------------------------------------

def test_monitor_pattern_filters_names():
    mon = mx.mon.Monitor(interval=1, pattern="fc.*")
    mon.tic()
    mon.stat_helper("fc1_output", mx.nd.array([1.0, 2.0, 3.0]))
    mon.stat_helper("conv0_output", mx.nd.array([4.0]))
    res = mon.toc()
    names = [k for _n, k, _v in res]
    assert "fc1_output" in names and "conv0_output" not in names


def test_monitor_inactive_outside_interval():
    mon = mx.mon.Monitor(interval=2)
    mon.tic()            # step 0: activates
    assert mon.activated
    mon.toc()
    mon.tic()            # step 1: interval 2 -> stays inactive
    assert not mon.activated
    mon.stat_helper("x_output", mx.nd.array([1.0]))
    assert mon.toc() == []


# -- log.get_logger formatter ------------------------------------------------

def test_get_logger_file_format(tmp_path):
    path = str(tmp_path / "run.log")
    logger = mx.log.get_logger("tlog_fmt", filename=path,
                               level=logging.DEBUG)
    logger.info("hello %d", 7)
    logger.warning("watch out")
    for h in logger.handlers:
        h.flush()
    with open(path) as f:
        lines = f.read().splitlines()
    # single-letter level + date + name] message, and no color codes in
    # file mode
    assert re.match(r"^I\d{4} \d{2}:\d{2}:\d{2} tlog_fmt\] hello 7$",
                    lines[0])
    assert lines[1].startswith("W") and "\x1b[" not in lines[1]


def test_get_logger_is_idempotent(tmp_path):
    path = str(tmp_path / "run2.log")
    a = mx.log.get_logger("tlog_once", filename=path)
    b = mx.log.get_logger("tlog_once", filename=path)
    assert a is b and len(a.handlers) == 1


# -- ProgressBar / Speedometer fixes ----------------------------------------

def test_progressbar_terminating_newline(capsys):
    bar = mx.callback.ProgressBar(total=2, length=10)
    bar(_Param(nbatch=1))
    out = capsys.readouterr().out
    assert out.endswith("\r") and "\n" not in out
    bar(_Param(nbatch=2))
    assert capsys.readouterr().out.endswith("\n")
    bar(_Param(nbatch=2))  # still done: no duplicate newline
    assert "\n" not in capsys.readouterr().out
    bar(_Param(nbatch=1))  # nbatch drop: next epoch re-arms the bar
    bar(_Param(nbatch=2))
    assert capsys.readouterr().out.endswith("\n")


def test_progressbar_length_and_total_clamped(capsys):
    bar = mx.callback.ProgressBar(total=4, length=0)
    assert bar.length == 1
    bar(_Param(nbatch=1))  # must not crash or emit a negative-width bar
    assert "[" in capsys.readouterr().out
    zero = mx.callback.ProgressBar(total=0, length=10)
    zero(_Param(nbatch=0))  # unknown batch count: no ZeroDivisionError
    assert "[" in capsys.readouterr().out


def test_speedometer_logs_smoothed_rate(caplog):
    sp = mx.callback.Speedometer(batch_size=8, frequent=1)
    with caplog.at_level(logging.INFO):
        sp(_Param(nbatch=0))
        sp(_Param(nbatch=1))
    assert "smoothed" in caplog.text


# -- print lint (graftlint; the check_print.py shim is gone) -----------------

def _run_check_print(path):
    return subprocess.run(
        [sys.executable, "-m", "ci.graftlint", "--pass", "print",
         str(path)], capture_output=True, text=True, cwd=ROOT, timeout=300)


def test_check_print_flags_bare_print(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text('x = 1\nprint("leak")\n')
    proc = _run_check_print(bad)
    assert proc.returncode == 1
    assert "bad.py:2" in proc.stdout


def test_check_print_honors_noqa_and_strings(tmp_path):
    ok = tmp_path / "ok.py"
    ok.write_text('s = "print(not a call)"\n'
                  'print("cli output")  # noqa: CLI entry point\n')
    assert _run_check_print(ok).returncode == 0


def test_check_print_clean_on_framework_tree():
    proc = subprocess.run(
        [sys.executable, "-m", "ci.graftlint", "--pass", "print"],
        capture_output=True, text=True, cwd=ROOT, timeout=300)
    assert proc.returncode == 0, proc.stdout


# -- env-docs lint (graftlint; the check_env_docs.py shim is gone) -----------

def _run_check_env_docs(*paths):
    return subprocess.run(
        [sys.executable, "-m", "ci.graftlint", "--pass", "env-docs"]
        + [str(p) for p in paths], capture_output=True, text=True,
        cwd=ROOT, timeout=300)


def test_check_env_docs_flags_undocumented_var(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text('import os\n'
                   'x = os.environ.get("MXNET_SURELY_UNDOCUMENTED_KNOB")\n')
    proc = _run_check_env_docs(bad)
    assert proc.returncode == 1
    assert "MXNET_SURELY_UNDOCUMENTED_KNOB" in proc.stdout
    assert "bad.py:2" in proc.stdout


def test_check_env_docs_ignores_prose_and_noqa(tmp_path):
    ok = tmp_path / "ok.py"
    ok.write_text(
        '"""Docstring mentioning MXNET_FAKE_DOCSTRING_ONLY is fine."""\n'
        '# comment: MXNET_FAKE_COMMENT_ONLY never trips AST constants\n'
        'y = os_environ_like("MXNET_FAKE_EXEMPTED")  # noqa: test-only\n')
    assert _run_check_env_docs(ok).returncode == 0, \
        _run_check_env_docs(ok).stdout


def test_check_env_docs_clean_on_framework_tree():
    """The canonical env-var doc covers every MXNET_* read in mxnet_tpu/
    (the drift this checker exists to stop)."""
    proc = _run_check_env_docs()
    assert proc.returncode == 0, proc.stdout
