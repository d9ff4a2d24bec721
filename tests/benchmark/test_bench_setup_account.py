"""The six ``setup.*`` per-layer metrics: one account of set-up
(``layer_metrics/setup.unattributed_s.py`` ``account``) over what the
program heard from JAX and the spans it records with tracing off.  Each
reader on a ``run`` put together by hand (None without a trace and on a
program that keeps no such records, as the parent commit; the cut at the
traffic's ramp; every second counted once, so the parts close to
``setup_s``), on the records a tiny engine's set-up leaves, and the
manifest's six entries."""

import os
import sys
import time

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from mxnet_tpu import compile_cache, tracing  # noqa: E402

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")
MANIFEST = harness.load_manifest()
SIX = ("setup.before_program_s", "setup.trace_s", "setup.lower_s",
       "setup.relower_s", "setup.lowerings_per_program",
       "setup.unattributed_s")


def _reader(name):
    return harness.find("layer_metrics", name).read


def _account(run):
    return harness.find("layer_metrics", "setup.unattributed_s").account(run)


@pytest.fixture(autouse=True)
def _clean():
    tracing.disable()
    tracing.reset()
    yield
    tracing.reset()


@pytest.mark.parametrize("name", SIX)
def test_manifest_has_the_metric_with_its_file_layer_and_cells(name):
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry["source"] == "program_counter"
    assert entry["moves"] == "setup_s" and entry["better"] == "lower"
    assert entry["unit"] == ("count" if name.endswith("program") else "s")
    # the layer as PERF.md's list of layers has it, letter for letter
    with open(os.path.join(ROOT, "PERF.md")) as f:
        assert "\n| %s |" % entry["layer"] in f.read()
    assert entry["layer"] == "executor and compile cache"
    assert entry["workloads"] == [c["name"] for c in MANIFEST["workloads"]]
    assert callable(_reader(name))
    # appended: what was there stays in front, in its order
    assert [m["name"] for m in MANIFEST["per_layer"][-6:]] == list(SIX)


# -- a run put together by hand ----------------------------------------------
# process start at 1000 s on the monotonic clock, set-up 50 s, ramp 20 s:
# the cut is at 1030
START, SETUP, RAMP = 1000.0, 50.0, 20.0
MAIN, LOOP = 11, 12     # thread ids


def _span(name, t0, t1, tid=MAIN):
    return {"name": name, "t0_ns": int(t0 * 1e9), "t1_ns": int(t1 * 1e9),
            "tid": tid, "attrs": {}, "span_id": name, "parent_id": None}


SPANS = [
    _span("setup.import", 1008.0, 1009.0),
    _span("serving.setup.pool", 1012.0, 1028.0),
    _span("serving.setup.engine", 1012.0, 1028.0),
    _span("serving.setup.program", 1013.0, 1027.0),
    _span("compile_cache.note_build", 1024.0, 1027.0),
    # in the ramp and in the window: not set-up's
    _span("module.setup.bind", 1035.0, 1036.0),
]
PHASES = [
    # the family's weights, between the import and the pool: no span
    ("trace", "init", 1009.5, 1010.0, MAIN),
    ("lower", "jit(init)", 1010.0, 1010.25, MAIN),
    ("load", "jit(init)", 1010.25, 1010.75, MAIN),
    # the program's first call: the trace holds an eager operation's
    # lowering and load, which are counted as those and not as trace
    ("lower", "jit(add)", 1014.0, 1014.5, MAIN),
    ("load", "jit(add)", 1014.5, 1015.0, MAIN),
    ("trace", "step", 1013.0, 1017.0, MAIN),
    ("lower", "jit(step)", 1017.0, 1019.0, MAIN),
    ("load", "jit(step)", 1019.0, 1020.0, MAIN),
    # for the fingerprint: a trace and a lowering inside note_build, and
    # another thread's trace at the same time, which is not
    ("trace", "step", 1024.0, 1025.0, MAIN),
    ("lower", "jit(step)", 1025.0, 1026.5, MAIN),
    ("trace", "loop", 1024.0, 1024.5, LOOP),
    # a request of the ramp and the reference's programs: cut off
    ("trace", "late", 1031.0, 1032.0, MAIN),
    ("lower", "jit(late)", 1032.0, 1033.0, MAIN),
    ("compile", "jit(late)", 1033.0, 1034.0, MAIN),
    ("compile", "jit(reference)", 1090.0, 1095.0, MAIN),
]
EXPECTED = {
    "setup.before_program_s": 8.0,
    # [1009.5, 1010] + [1013, 1017] less the eager second + [1024, 1025]
    "setup.trace_s": 0.5 + 3.0 + 1.0,
    "setup.lower_s": 0.25 + 0.5 + 2.0 + 1.5,
    "setup.relower_s": 1.0 + 1.5,
    "setup.lowerings_per_program": 4 / 3,
    # 30 s before the ramp less 8 before, 1 import, 16 pool, 1.25 weights
    "setup.unattributed_s": 30.0 - 8.0 - 1.0 - 16.0 - 1.25,
}


@pytest.fixture
def by_hand(monkeypatch):
    monkeypatch.setattr(compile_cache, "phases", lambda: list(PHASES))
    monkeypatch.setattr(tracing, "setup_spans",
                        lambda: [dict(s) for s in SPANS])
    return {"trace": {"window_s": 3.0}, "setup_s": SETUP,
            "traffic": {"ramp_seconds": RAMP},
            "window": {"t0": START + SETUP, "t_end": START + SETUP + 30}}


@pytest.mark.parametrize("name", SIX)
def test_reader_on_a_run_put_together_by_hand(name, by_hand):
    assert _reader(name)(by_hand) == pytest.approx(EXPECTED[name],
                                                   abs=1e-6)
    assert _reader(name)(dict(by_hand, trace=None)) is None


def test_the_parts_close_to_setup_s_with_every_second_counted_once(
        by_hand):
    parts = _account(by_hand)
    assert parts["load_s"] == pytest.approx(0.5 + 0.5 + 1.0)
    # the spans' own remainder: import 1, pool 16, less what has a name
    assert parts["spans_s"] == pytest.approx(
        17.0 - (3.0 + 1.0) - (0.5 + 2.0 + 1.5) - (0.5 + 1.0))
    assert sum(parts[k] for k in (
        "before_program_s", "trace_s", "lower_s", "load_s", "spans_s",
        "ramp_s", "unattributed_s")) == pytest.approx(SETUP, abs=1e-9)
    # a traffic file with no ramp: the cut is the window's opening
    no_ramp = dict(by_hand, traffic={})
    late = _account(no_ramp)
    assert late["ramp_s"] == 0.0 and late["programs"] == 4
    assert late["lowerings"] == 5
    assert sum(late[k] for k in (
        "before_program_s", "trace_s", "lower_s", "load_s", "spans_s",
        "unattributed_s")) == pytest.approx(SETUP, abs=1e-9)


@pytest.mark.parametrize("name", SIX)
def test_reader_reads_nothing_from_a_program_without_the_records(
        name, by_hand, monkeypatch):
    """The parent commit has no ``compile_cache.phases`` and no
    ``tracing.setup_spans``; a process that never imported the program
    has no ``setup.import`` span.  Nothing is read, nothing raises."""
    monkeypatch.setattr(tracing, "setup_spans", lambda: [
        dict(s) for s in SPANS if s["name"] != "setup.import"])
    assert _reader(name)(by_hand) is None
    monkeypatch.delattr(tracing, "setup_spans")
    assert _reader(name)(by_hand) is None
    monkeypatch.undo()
    monkeypatch.delattr(compile_cache, "phases")
    assert _reader(name)(by_hand) is None


def test_the_account_of_a_tiny_engines_setup():
    """The records a real set-up leaves: the parts close to the seconds
    since the stand-in for the process's start, the engine's spans are the
    largest named part and the loads are ``exec.setup_load_s``'s, each
    second once."""
    t_process = time.monotonic()
    # the import's span, as a process of its own would have left it
    sp = tracing.setup_span("setup.import")
    sp.end()
    family = harness.find("families", "decode_engine")
    system = family.System(harness.load_json(os.path.join(
        TINY, "lm_tiny.json")), {}, 7, jax.devices()[:1])
    t0 = time.monotonic()
    system.close()
    run = {"trace": {"window_s": 3.0}, "setup_s": t0 - t_process,
           "traffic": {"ramp_seconds": 0.0},
           "window": {"t0": t0, "t_end": t0 + 1.0}}
    parts = _account(run)
    assert sum(parts[k] for k in (
        "before_program_s", "trace_s", "lower_s", "load_s", "spans_s",
        "unattributed_s")) == pytest.approx(run["setup_s"], abs=1e-6)
    # a union where exec.setup_load_s is a sum: the engine's loop thread
    # compiles its first small programs while the main thread compiles
    summed = sum(seconds for at, seconds, _hit in compile_cache.programs()
                 if t_process <= at < t0)
    assert 0.5 * summed < parts["load_s"] <= summed + 1e-6
    assert summed <= _reader("exec.setup_load_s")(run)  # the process's
    assert parts["programs"] >= 3 and parts["trace_s"] > 0 \
        and parts["lower_s"] > 0
    assert parts["spans_s"] > 0
    assert 0 <= parts["unattributed_s"] < run["setup_s"]
    assert _reader("setup.lowerings_per_program")(run) \
        == parts["lowerings"] / parts["programs"] >= 1.0
    assert tracing.spans_recent(1 << 20) == []
