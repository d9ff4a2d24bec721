"""Set-up's account, the program's side: what ``compile_cache`` keeps of
JAX's own monitoring events (``phases()``, the ``stats()`` fields, and
``programs()`` as a view of the same store) and the spans that record with
tracing off (``tracing.setup_span``: the import, an engine's build and each
program's first call, ``note_build``, a module's ``bind`` / ``init_params`` /
``init_optimizer``).  The mechanism only listens: a tiny engine's build
publishes exactly as many lowerings as the parent commit's, tracing off or
on.  ``tests/benchmark/test_bench_setup_account.py`` has the readers."""

import importlib
import json
import threading
import time

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compile_cache, perfdebug, tracing
from mxnet_tpu.models import transformer_lm as tlm
from mxnet_tpu.serving import DecodeEngine, lm_pool

CFG = tlm.LMConfig(vocab=50, embed=16, heads=2, layers=2, ffn=32,
                   max_len=32, eos_id=1)
BUCKETS = (8, 16)
#: what a tiny engine's build lowers once the eager operations of an
#: earlier build are cached, read on the parent commit (fde449e) with a
#: listener of the test's own: the two prefill buckets and the step, each
#: once, and nothing else
PARENT_LOWERINGS = ["jit(prefill)", "jit(prefill)", "jit(step)"]


@pytest.fixture(autouse=True)
def _clean():
    tracing.disable()
    tracing.reset()
    yield
    tracing.disable()
    tracing.reset()


@pytest.fixture(scope="module")
def params():
    return tlm.init_params(CFG, 0)


def _engine(params, **kw):
    return DecodeEngine(CFG, params, slots=3, prefill_buckets=BUCKETS,
                        autostart=False, name="tiny", **kw)


def _since(t):
    """The records that ended at ``t`` (``time.monotonic()``) or later: the
    store is bounded, and full in a process that ran many tests."""
    return [r for r in compile_cache.phases() if r[3] >= t]


def test_what_a_process_does_later_pushes_out_none_of_its_first_records(
        caplog):
    """The store keeps the first records and the newest, counts those it
    dropped between them and says so once, aloud: set-up's records outlive
    a reference check that compiles thousands of programs after it."""
    room = compile_cache._PHASES_ROOM
    held = compile_cache._Phases()
    assert held.last() is None and held.all() == []
    with caplog.at_level("WARNING", logger="mxnet_tpu.compile_cache"):
        for i in range(3 * room + 7):
            held.append(("lower", "f", i, i, 0))
    kept = held.all()
    assert [r[2] for r in kept[:room]] == list(range(room))
    assert [r[2] for r in kept[room:]] == list(
        range(2 * room + 7, 3 * room + 7))
    assert held.dropped == room + 7
    assert len([r for r in caplog.records if "dropped" in r.message]) == 1
    held.pop()
    assert held.last()[2] == 3 * room + 5
    held.restore(kept[:5])
    assert (held.all(), held.dropped) == (kept[:5], 0)
    held.pop()
    held.append(kept[9])
    assert held.all() == kept[:4] + [kept[9]]
    assert compile_cache.stats()["phases_dropped"] == \
        compile_cache._phases.dropped


def _called_once():
    """One jitted call of a function no test has called before, one
    jitted function inside it called twice; (records it left, the sum and
    the number of the trace durations JAX published meanwhile, ``stats()``
    just before the call)."""
    published = []

    def listen(event, duration, **_kw):
        if event == compile_cache._EVENT_TRACE:
            published.append(duration)

    @jax.jit
    def inner(x):
        return jnp.tanh(x) * 2 + 1

    @jax.jit
    def outer(x):
        return inner(x) + inner(x + 1)

    x = jnp.ones((5,))      # an eager operation leaves records of its own
    n, s0 = time.monotonic(), compile_cache.stats()
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        outer(x).block_until_ready()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    return _since(n), sum(published), len(published), s0


@pytest.mark.parametrize("phase, name", [
    ("trace", "outer"), ("lower", "jit(outer)"), ("compile", "jit(outer)")])
def test_phases_hold_one_named_record_a_phase_of_one_jitted_call(phase,
                                                                 name):
    before = time.monotonic()
    records, _sum, _n, _s0 = _called_once()
    after = time.monotonic()
    mine = [r for r in records if r[0] == phase]
    assert [r[1] for r in mine] == [name]
    _phase, _name, t0, t1, tid = mine[0]
    assert before <= t0 <= t1 <= after
    assert tid == threading.get_native_id()
    # trace, then lowering, then XLA: in that order on the one clock
    order = [r[0] for r in sorted(records, key=lambda r: r[3])]
    assert order == ["trace", "lower", "compile"]


def test_nested_traces_are_one_record_whose_length_is_their_union():
    records, published_sum, published, s0 = _called_once()
    s1 = compile_cache.stats()
    (kept,) = [r for r in records if r[0] == "trace"]
    # JAX published the inner function's traces and every jnp call's
    assert published > 5
    length = kept[3] - kept[2]
    assert length < published_sum           # the union is not the sum
    assert s1["trace_seconds"] - s0["trace_seconds"] == pytest.approx(
        length, abs=1e-5)
    assert s1["lowerings"] - s0["lowerings"] == 1
    assert s1["lower_seconds"] > s0["lower_seconds"]


def test_programs_is_a_view_of_the_same_store():
    records, _sum, _n, s0 = _called_once()
    (xla,) = [r for r in records if r[0] in ("load", "compile")]
    at, seconds, hit = compile_cache.programs()[-1]
    assert (at, hit) == (xla[3], False)     # the suite runs with no cache
    assert seconds == pytest.approx(xla[3] - xla[2])
    assert compile_cache.stats()["program_seconds"] \
        - s0["program_seconds"] == pytest.approx(seconds, abs=1e-5)


def test_a_lowering_takes_the_place_of_the_traces_inside_it():
    """A kernel's lowering traces some hundreds of small functions, each
    published before the lowering that holds it."""
    tid = threading.get_native_id()
    n = time.monotonic()
    s0 = compile_cache.stats()
    for _ in range(3):
        compile_cache._on_duration(compile_cache._EVENT_TRACE, 1e-4,
                                   fun_name="less")
    compile_cache._on_duration(compile_cache._EVENT_LOWER, 0.5,
                               fun_name="jit(kernel)")
    records = _since(n)
    assert [(r[0], r[1]) for r in records] == [("lower", "jit(kernel)")]
    assert records[0][4] == tid
    s1 = compile_cache.stats()
    assert s1["trace_seconds"] == pytest.approx(s0["trace_seconds"],
                                                abs=1e-9)
    assert s1["lower_seconds"] - s0["lower_seconds"] == pytest.approx(0.5)


@pytest.fixture(scope="module")
def built(params):
    """One tiny engine built with tracing off: the records its build
    left.  Built once before, so that the eager operations of a build
    (zeros, casts) are in JAX's caches whatever ran before in the
    process."""
    tracing.disable()
    tracing.reset()
    _engine(params).close()
    tracing.reset()
    n = time.monotonic()
    engine = _engine(params)
    out = {"engine": engine, "phases": _since(n),
           "spans": tracing.setup_spans()}
    yield out
    engine.close()


@pytest.mark.parametrize("traced", [False, True], ids=["off", "on"])
def test_an_engines_build_lowers_exactly_what_the_parents_does(params,
                                                               built,
                                                               traced):
    if traced:
        tracing.enable()
    n = time.monotonic()
    lowerings = compile_cache.stats()["lowerings"]
    _engine(params).close()
    lowered = [r[1] for r in _since(n) if r[0] == "lower"]
    assert lowered == PARENT_LOWERINGS
    assert compile_cache.stats()["lowerings"] - lowerings == 3
    assert [r[1] for r in built["phases"] if r[0] == "lower"] \
        == PARENT_LOWERINGS


def test_setup_spans_record_with_tracing_off_and_nest(built):
    spans = built["spans"]
    by_id = {r["span_id"]: r for r in spans}

    def parent(r):
        return by_id.get(r["parent_id"], {}).get("name")

    (engine,) = [r for r in spans if r["name"] == "serving.setup.engine"]
    assert engine["attrs"] == {"model": "tiny", "replica": "0", "slots": 3,
                               "buckets": list(BUCKETS)}
    assert engine["parent_id"] is None and engine["status"] == "ok"
    for name in ("state", "warm"):
        (r,) = [r for r in spans if r["name"] == "serving.setup." + name]
        assert parent(r) == "serving.setup.engine"
        assert engine["t0_ns"] <= r["t0_ns"] <= r["t1_ns"] \
            <= engine["t1_ns"]
    programs = [r for r in spans if r["name"] == "serving.setup.program"]
    assert [(r["attrs"]["kind"], r["attrs"]["bucket"]) for r in programs] \
        == [("decode_prefill", 8), ("decode_prefill", 16),
            ("decode_step", None)]
    assert {parent(r) for r in programs} == {"serving.setup.warm"}
    # whose trace it was: each program's trace and lowering lie inside
    # its first call's span
    for r, fn in zip(programs, ("prefill", "prefill", "step")):
        inside = [p[1] for p in built["phases"] if p[0] != "compile"
                  and r["t0_ns"] <= p[2] * 1e9 and p[3] * 1e9 <= r["t1_ns"]]
        assert inside == [fn, "jit(%s)" % fn]
    # nothing of it is what tracing itself recorded
    assert tracing.spans_recent(1 << 20) == []


def test_an_engines_loop_with_tracing_off_still_records_nothing(built):
    engine = built["engine"]
    engine.start()
    tokens = engine.generate(np.array([5, 7, 9], np.int32),
                             max_new_tokens=3)
    assert len(tokens) >= 1
    assert tracing.spans_recent(1 << 20) == []
    assert tracing.setup_spans() == []      # and no set-up span either


def test_note_build_and_the_pool_are_spans_when_builds_are_recorded(
        params, tmp_path):
    compile_cache.enable(str(tmp_path / "cc"))
    try:
        pool = lm_pool(CFG, params, n_replicas=1, name="tiny-pool",
                       engine_opts={"slots": 3,
                                    "prefill_buckets": BUCKETS})
        pool.close()
    finally:
        compile_cache.disable()
        compile_cache.reset_records()
    spans = tracing.setup_spans()
    by_id = {r["span_id"]: r for r in spans}
    (root,) = [r for r in spans if r["parent_id"] is None]
    assert root["name"] == "serving.setup.pool"
    assert root["attrs"] == {"model": "tiny-pool"}
    (engine,) = [r for r in spans if r["name"] == "serving.setup.engine"]
    assert engine["parent_id"] == root["span_id"]
    notes = [r for r in spans if r["name"] == "compile_cache.note_build"]
    assert [r["attrs"] for r in notes] == [
        {"exec": "serving:tiny-pool", "kind": "decode_prefill"}] * 2 + [
        {"exec": "serving:tiny-pool", "kind": "decode_step"}]
    assert {by_id[r["parent_id"]]["name"] for r in notes} \
        == {"serving.setup.program"}
    assert tracing.tree(root["trace_id"])["n_spans"] == len(spans)


def test_a_modules_setup_is_three_spans_and_a_second_call_none():
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=4, name="fc"),
        name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    for _ in range(2):      # fit() calls all three again: early returns
        mod.bind(data_shapes=[("data", (4, 6))],
                 label_shapes=[("softmax_label", (4,))])
        mod.init_params()
        mod.init_optimizer()
    assert [r["name"] for r in tracing.setup_spans()] == [
        "module.setup.bind", "module.setup.init_params",
        "module.setup.init_optimizer"]


def test_the_import_is_a_span_from_its_first_line():
    before = time.monotonic_ns()
    importlib.reload(mx)
    (span,) = tracing.setup_spans()
    assert span["name"] == "setup.import" and span["parent_id"] is None
    assert before <= span["t0_ns"] == mx._IMPORT_T0_NS <= span["t1_ns"]
    assert tracing.current() is None


def test_setup_span_with_tracing_on_parents_what_opens_under_it():
    tracing.enable()
    with tracing.setup_span("serving.setup.engine", model="m") as sp:
        with tracing.start_span("host_read", site="x") as child:
            pass
    assert child.parent_id == sp.span_id
    assert [r["name"] for r in tracing.spans_recent()] == ["host_read"]
    assert [r["name"] for r in tracing.setup_spans()] \
        == ["serving.setup.engine"]
    assert tracing.tree(sp.trace_id)["n_spans"] == 2


def test_a_flight_dump_holds_the_setup_spans_with_tracing_off(tmp_path,
                                                              monkeypatch):
    monkeypatch.setenv("MXNET_FLIGHT_RECORDER_DIR", str(tmp_path))
    with tracing.setup_span("serving.setup.engine", model="m"):
        pass
    with open(perfdebug.flight_dump("manual")) as f:
        path = json.load(f)["span_dump"]
    with open(path) as f:
        (record,) = [json.loads(line) for line in f]
    assert record["name"] == "serving.setup.engine"
    assert record["attrs"] == {"model": "m"}
