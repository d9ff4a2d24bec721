"""The five per-layer metrics that read the program's own spans and counts
(``fit.host_ms_per_step``, ``fit.host_reads_per_step``,
``decode.host_ms_per_step``, ``decode.admit_host_ms``,
``exec.setup_load_s``): each reader against spans that the tiny cells leave
under a CPU profile session, with a ``run["trace"]`` put together by hand
(the CPU has no device plane for ``trace_reduce``), and against a program
that has no such spans, as the parent commit has not."""

import os
import sys
import time

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from mxnet_tpu import compile_cache, tracing  # noqa: E402

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")
MANIFEST = harness.load_manifest()
NEW = ("fit.host_ms_per_step", "fit.host_reads_per_step",
       "decode.host_ms_per_step", "decode.admit_host_ms",
       "exec.setup_load_s")


def _reader(name):
    return harness.find("layer_metrics", name).read


def _tiny(name):
    return harness.load_json(os.path.join(TINY, name))


class _Session:
    """Stands in for ``harness.Tracer``: a CPU profile session from
    ``start_after`` seconds into the window until ``close``."""

    def __init__(self, directory, counters, start_after=0.0):
        self.dir, self.counters = str(directory), counters
        self.start_after = start_after
        self.t_start = self.t_stop = None

    def poll(self, elapsed):
        if self.t_start is None and elapsed >= self.start_after:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.t_start = time.monotonic()
            self.start = self.counters()

    def close(self):
        self.stop = self.counters()
        self.t_stop = time.monotonic()
        jax.profiler.stop_trace()

    def trace(self, modules=()):
        return {"window_s": self.t_stop - self.t_start,
                "counted": {k: v - self.start[k]
                            for k, v in self.stop.items()},
                "devices": [{"modules": list(modules)}]}


@pytest.fixture(autouse=True)
def _clean():
    tracing.disable()
    tracing.reset()
    yield
    tracing.reset()


def test_manifest_has_the_five_and_each_has_its_file():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf_md = f.read()
    for name in NEW:
        entry = by_name[name]
        assert entry["source"] == "program_counter"
        # the layer as PERF.md's list of layers has it, letter for letter
        assert "\n| %s |" % entry["layer"] in perf_md
        assert callable(_reader(name))
    assert "workloads" not in by_name["exec.setup_load_s"]   # every cell


@pytest.mark.parametrize("name", NEW)
def test_reader_returns_none_without_a_trace_or_without_spans(name):
    window = {"t0": time.monotonic() - 1e6, "t_end": time.monotonic()}
    assert _reader(name)({"trace": None, "window": window}) is None
    if name != "exec.setup_load_s":
        trace = {"window_s": 3.0, "counted": {"steps": 5},
                 "devices": [{"modules": [("jit_step", 0.0, 0.001)]}]}
        assert _reader(name)({"trace": trace, "window": window}) is None


def test_readers_skip_a_program_whose_records_are_on_another_clock(
        monkeypatch):
    """The parent commit's records have no ``t1_ns``, and its compile cache
    no list of programs: nothing is read, nothing raises."""
    now = time.monotonic()
    old = {"name": "fit.batch", "t0": time.time() - 1, "t1": time.time(),
           "dur_s": 1.0, "attrs": {}, "span_id": "a", "parent_id": None}
    monkeypatch.setattr(tracing, "spans_recent", lambda n=1000: [dict(old)])
    monkeypatch.delattr(compile_cache, "programs")
    run = {"trace": {"window_s": 3.0, "counted": {"steps": 5},
                     "devices": [{"modules": []}]},
           "window": {"t0": now - 30, "t_end": now}}
    for name in NEW:
        assert _reader(name)(run) is None


def test_fit_readers_on_the_spans_of_a_tiny_fit(tmp_path):
    family = harness.find("families", "module_fit")
    system = family.System(_tiny("resnet_tiny.json"), _tiny("fit_tiny.json"),
                           7, jax.devices()[:1])
    assert tracing.spans_recent() == []         # set-up recorded nothing
    session = _Session(tmp_path, system.counters, start_after=0.3)
    window = system.train(1.0, session)
    system.close()
    run = {"trace": session.trace(), "window": window}
    steps = run["trace"]["counted"]["steps"]
    assert steps >= 2
    per_step = _reader("fit.host_ms_per_step")(run)
    reads = _reader("fit.host_reads_per_step")(run)
    # the tiny cell's Speedometer reads the metric every 20 batches
    assert 0 <= reads <= 0.2 and reads == pytest.approx(
        round(reads * steps) / steps)
    assert 0 < per_step <= 1e3 * run["trace"]["window_s"] / steps
    if reads:       # a read is time the host did not spend
        assert per_step < 1e3 * run["trace"]["window_s"] / steps
    # set-up's programs were heard, one by one, before the window opened
    loads = _reader("exec.setup_load_s")(run)
    assert 0 < loads < window["t0"]
    during = [p for p in compile_cache.programs()
              if window["t0"] <= p[0] <= window["t_end"]]
    assert not during                            # warm-up belongs to set-up
    for name in ("decode.host_ms_per_step", "decode.admit_host_ms"):
        assert _reader(name)(run) is None


def test_decode_readers_on_the_spans_of_a_tiny_engine(tmp_path):
    family = harness.find("families", "decode_engine")
    system = family.System(_tiny("lm_tiny.json"), {}, 7, jax.devices()[:1])
    assert tracing.spans_recent() == []
    session = _Session(tmp_path, system.counters)
    t0 = time.monotonic()
    session.poll(0.0)
    handles = [system.submit(np.arange(2, 2 + n, dtype=np.int32), 6, None)
               for n in (3, 9, 4, 6, 5)]
    for h in handles:
        assert system.wait(h, 60) is None
    t_end = time.monotonic()
    session.close()
    system.close()
    # a device step of no length: the whole iteration is the host's
    run = {"trace": session.trace([("jit_step", 0.0, 0.0),
                                   ("jit_prefill", 0.0, 0.001)]),
           "window": {"t0": t0, "t_end": t_end}}
    assert run["trace"]["counted"]["decode_steps"] >= 5
    host = _reader("decode.host_ms_per_step")(run)
    admit = _reader("decode.admit_host_ms")(run)
    assert 0 < host < 1e3 * (t_end - t0)
    assert 0 < admit < 1e3 * (t_end - t0)
    iters = [r for r in tracing.spans_recent(1 << 20)
             if r["name"] == "serving.decode.iter"
             and r["attrs"]["admits"] == 0]
    assert host == pytest.approx(
        1e3 * sorted(r["dur_s"] for r in iters)[len(iters) // 2], rel=0.5)
    # the step's device time comes off
    run["trace"]["devices"][0]["modules"][0] = ("jit_step", 0.0, 1e-4)
    assert _reader("decode.host_ms_per_step")(run) == pytest.approx(
        host - 0.1)
    for name in ("fit.host_ms_per_step", "fit.host_reads_per_step"):
        assert _reader(name)(run) is None
    assert _reader("exec.setup_load_s")(run) > 0
