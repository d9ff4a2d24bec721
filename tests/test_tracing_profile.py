"""Program spans on the device profile's clock (docs/observability.md "One
clock, two sinks"): a stacked span is a ``jax.profiler.TraceAnnotation``
while a profile is being taken and tracing switches itself on and off with
the session; the loops' spans of ``fit`` and of the decode engine nest as
``PERF.md`` section 3 lists them; a busy loop's spans do not push a
finished request's tree out of its ring; the program names the
benchmark's readers match stay ``jit_step`` and ``jit_prefill``."""

import glob
import os
import threading
import time
import timeit

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compile_cache, telemetry, tracing
from mxnet_tpu.models import transformer_lm as tlm
from mxnet_tpu.serving import DecodeEngine

# the sizes of tests/benchmark/tiny/lm_tiny.json
CFG = tlm.LMConfig(256, 64, 4, 2, 256, 64, eos_id=256)
SLOTS, BUCKETS = 3, (8, 32)


@pytest.fixture(autouse=True)
def _clean():
    tracing.disable()
    tracing.reset()
    yield
    tracing.disable()
    tracing.reset()


@pytest.fixture(scope="module")
def params():
    return tlm.init_params(CFG, seed=3)


@pytest.fixture
def engine(params):
    eng = DecodeEngine(CFG, params, slots=SLOTS, prefill_buckets=BUCKETS,
                       name="tiny")
    eng.start()
    yield eng
    eng.close(drain=False)


class _Profile:
    """A CPU profile session with the Python tracer off, as the benchmark's
    traced runs take it; ``events()`` reads the ``.xplane.pb`` back."""

    def __init__(self, directory):
        self.dir = str(directory)

    def __enter__(self):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()

    def events(self):
        """{event name: [(thread line, stats dict)]} of the ``mx.`` events."""
        from jax.profiler import ProfileData

        (path,) = glob.glob(os.path.join(self.dir, "plugins", "profile",
                                         "*", "*.xplane.pb"))
        out = {}
        for plane in ProfileData.from_file(path).planes:
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith("mx."):
                        out.setdefault(e.name, []).append(
                            ((plane.name, i), dict(e.stats)))
        return out


def _children(spans):
    """{(parent name or None, child name): count}."""
    by_id = {r["span_id"]: r for r in spans}
    out = {}
    for r in spans:
        parent = by_id.get(r["parent_id"])
        key = (parent["name"] if parent else None, r["name"])
        out[key] = out.get(key, 0) + 1
    return out


# -- one clock, two sinks, self-starting -------------------------------------

def test_stacked_span_is_an_annotation_of_the_profile_and_recording_stops(
        tmp_path):
    assert not tracing.enabled()
    assert tracing.start_span("before") is tracing.NULL_SPAN
    with _Profile(tmp_path) as prof:
        assert tracing.enabled()        # by the session alone
        t_lo = time.monotonic_ns()
        with tracing.start_span("probe.outer", loop=True, site="here") as sp:
            with tracing.start_span("probe.inner"):
                pass
            sp.annotate(found=3)
        away = tracing.start_span("probe.away", stack=False)
        t = threading.Thread(target=away.end)
        t.start()
        t.join(10)
        assert not t.is_alive()
        t_hi = time.monotonic_ns()
    assert not tracing.enabled()
    assert tracing.start_span("after") is tracing.NULL_SPAN
    events = prof.events()
    (outer,), (inner,) = events["mx.probe.outer"], events["mx.probe.inner"]
    assert outer[1]["site"] == "here" and outer[1]["found"] == 3
    assert outer[0] == inner[0]         # one thread's line
    assert "mx.probe.away" not in events
    recs = {r["name"]: r for r in tracing.spans_recent()}
    assert set(recs) == {"probe.outer", "probe.inner", "probe.away"}
    me = threading.get_native_id()
    assert all(r["tid"] == me for r in recs.values())
    for r in recs.values():             # the monotonic clock, whole ns
        assert t_lo <= r["t0_ns"] <= r["t1_ns"] <= t_hi
        assert abs(r["dur_s"] - (r["t1_ns"] - r["t0_ns"]) * 1e-9) < 1e-6
        assert abs(r["t0"] - time.time()) < 60
    assert recs["probe.inner"]["parent_id"] == recs["probe.outer"]["span_id"]


def test_start_span_and_phase_cost_under_5us_when_nothing_is_on():
    assert not tracing.enabled() and not telemetry.enabled()

    def span():
        tracing.start_span("fit.batch", loop=True, epoch=0).end("ok")

    def phase():
        with telemetry.phase("data"):
            pass

    n = 20000
    for fn in (span, phase):
        best = min(timeit.repeat(fn, number=n, repeat=5)) / n
        assert best < 5e-6, "%s: %.2f us a call" % (fn.__name__, best * 1e6)
    assert tracing.spans_recent() == []


def test_phase_is_a_span_under_the_current_one_and_times_without_tracing():
    tracing.enable()
    with tracing.start_span("fit.batch", loop=True) as batch:
        with telemetry.phase("update"):
            pass
    with telemetry.phase("barrier", family="kvstore"):
        pass
    recs = {r["name"]: r for r in tracing.spans_recent()}
    assert recs["fit.update"]["parent_id"] == batch.span_id
    assert recs["kvstore.barrier"]["parent_id"] is None
    tracing.disable()
    tracing.reset()
    # telemetry alone: the histogram and the hooks get the span's length,
    # and nothing is recorded as a span
    seen = []
    hook = telemetry.add_phase_hook(lambda f, p, s: seen.append((f, p, s)))
    telemetry.enable()
    try:
        with telemetry.phase("probe_timed"):
            time.sleep(0.002)
        total, count = telemetry.phase_totals("fit")["probe_timed"]
    finally:
        telemetry.disable()
        telemetry.remove_phase_hook(hook)
    assert count == 1 and 0.002 <= total < 1.0
    assert seen == [("fit", "probe_timed", total)]
    assert tracing.spans_recent() == []


def test_frame_ends_the_spans_a_raising_block_left_open():
    tracing.enable()
    with pytest.raises(RuntimeError):
        with tracing.frame():
            tracing.start_span("fit.batch", loop=True)
            tracing.start_span("fit.update")
            raise RuntimeError("mid-batch")
    assert tracing.current() is None
    assert {r["name"]: r["status"] for r in tracing.spans_recent()} == {
        "fit.batch": "error", "fit.update": "error"}


def test_loop_spans_leave_a_finished_request_tree_readable():
    tracing.enable()
    root = tracing.start_span("serving.generate", stack=False)
    with tracing.start_span("serving.admit", parent=root):
        with tracing.host_read("prefill.first_token"):
            pass
    root.end("ok")
    for i in range(10000):      # a busy engine: about 100 a second
        with tracing.start_span("serving.decode.iter", loop=True):
            with tracing.start_span("serving.decode.step"):
                pass
    tr = tracing.tree(root.trace_id)
    assert tr["complete"] and tr["n_spans"] == 3
    assert tr["root"]["children"][0]["children"][0]["name"] == "host_read"
    # ... and the loop's own ring is bounded like the other
    loops = [r for r in tracing.spans_recent(1 << 20)
             if r["name"] == "serving.decode.iter"]
    assert 0 < len(loops) <= 4096


# -- the program names the benchmark's readers match ---------------------------

def test_step_and_prefill_lower_to_the_pinned_module_names(engine, params):
    import jax.numpy as jnp

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    kv = sds((SLOTS, CFG.max_len, CFG.heads, CFG.embed // CFG.heads),
             jnp.float32)
    state = (tuple(kv for _ in range(CFG.layers)),
             tuple(kv for _ in range(CFG.layers)),
             sds((SLOTS,), jnp.int32), sds((SLOTS,), jnp.int32),
             sds((SLOTS,), jnp.int32), sds((SLOTS,), jnp.bool_),
             sds((SLOTS,), jnp.float32), sds((SLOTS,), jnp.uint32))
    shapes = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), params)
    step = engine._step_fn.lower(shapes, state, sds((SLOTS,), jnp.bool_))
    assert "module @jit_step " in step.as_text()
    assert set(engine._prefill_fns) == set(BUCKETS)
    for bucket, fn in engine._prefill_fns.items():
        lowered = fn.lower(
            shapes, state, sds((bucket,), jnp.int32), sds((), jnp.int32),
            sds((), jnp.int32), sds((), jnp.int32), sds((), jnp.float32),
            sds((), jnp.uint32), sds((), jnp.bool_))
        assert "module @jit_prefill " in lowered.as_text()


# -- the loops' spans --------------------------------------------------------

def _rest(engine):
    """A session's last step is followed by one it rides inactive: wait
    until the loop has read that one too and rests."""
    steps = -1
    while steps != engine.steps or engine.pending_rows():
        steps = engine.steps
        time.sleep(0.1)


def test_decode_engine_leaves_its_loop_spans_under_a_profile(engine,
                                                             tmp_path):
    engine.generate(np.array([5, 7, 9], np.int32), max_new_tokens=2)
    _rest(engine)
    assert tracing.spans_recent() == []     # nothing on: nothing recorded
    with _Profile(tmp_path) as prof:
        sessions = [engine.submit(np.arange(2, 2 + n, dtype=np.int32),
                                  max_new_tokens=5) for n in (3, 9, 4, 6)]
        for s in sessions:
            s.result(60)
        _rest(engine)
    spans = tracing.spans_recent(1 << 20)
    nest = _children(spans)
    iters = nest[(None, "serving.decode.iter")]
    assert iters >= 5
    assert nest[("serving.decode.iter", "serving.decode.queue")] == iters
    steps = nest[("serving.decode.iter", "serving.decode.step")]
    assert 5 <= steps <= iters
    # the engine never rested in between, so one pipeline ran from end to
    # end: the first turn dispatches and has nothing to read, the last
    # reads and dispatches nothing, every other one does both
    for child in ("serving.decode.dispatch", "host_read",
                  "serving.decode.fanout"):
        assert nest[("serving.decode.step", child)] == steps - 1
    assert nest[("serving.generate", "serving.admit")] == 4
    assert nest[("serving.admit", "serving.prefill.dispatch")] == 4
    # an admission's first token is read outside its span, under the
    # iteration, after the iteration's step was dispatched
    assert ("serving.admit", "host_read") not in nest
    assert nest[("serving.decode.iter", "host_read")] == 4
    by_name = {}
    for r in spans:
        by_name.setdefault(r["name"], []).append(r)
    assert {r["attrs"]["site"] for r in by_name["host_read"]} == {
        "decode.packed", "prefill.first_token"}
    assert sum(r["attrs"]["admits"]
               for r in by_name["serving.decode.iter"]) == 4
    # within a turn: the dispatch of the next step, then the packed read
    # of the one before, then the first tokens; ``ahead`` says so
    assert sum(r["attrs"]["ahead"]
               for r in by_name["serving.decode.iter"]) == steps - 2
    for it in by_name["serving.decode.iter"]:
        mine = [r for r in spans if r["t0_ns"] >= it["t0_ns"]
                and r["t1_ns"] <= it["t1_ns"] and r["tid"] == it["tid"]]
        sent = [r["t1_ns"] for r in mine
                if r["name"] == "serving.decode.dispatch"]
        reads = [r for r in mine if r["name"] == "host_read"]
        assert len(sent) <= 1
        assert it["attrs"]["ahead"] == int(bool(sent) and any(
            r["attrs"]["site"] == "decode.packed" for r in reads))
        if it["attrs"]["active"]:
            assert sent and all(sent[0] <= r["t0_ns"] for r in reads)
        sites = [r["attrs"]["site"]
                 for r in sorted(reads, key=lambda r: r["t0_ns"])]
        assert sites == sorted(sites), sites     # decode.packed first
    for r in by_name["serving.admit"]:
        assert r["attrs"]["bucket"] in BUCKETS
        assert r["attrs"]["queue_wait_ms"] >= 0 and not r["attrs"]["resumed"]
    # the engine's thread, and not the submitting one
    (loop_tid,) = {r["tid"] for r in by_name["serving.decode.iter"]}
    assert loop_tid != threading.get_native_id()
    assert {r["tid"] for r in by_name["serving.admit"]} == {loop_tid}
    events = prof.events()
    for name in ("serving.decode.iter", "serving.decode.step",
                 "serving.decode.dispatch", "serving.decode.fanout",
                 "serving.admit", "serving.prefill.dispatch", "host_read"):
        assert "mx." + name in events, name
    assert "mx.serving.generate" not in events  # ends on another thread
    # the admission timestamp splits a first-token wait
    for s in sessions:
        assert s.t_submit <= s.t_admit <= s.t_first
        assert 0 <= s.queue_wait() <= s.ttft()
        assert s.admit_step is not None
    # an idle engine records nothing more
    n = len(tracing.spans_recent(1 << 20))
    time.sleep(0.1)
    assert len(tracing.spans_recent(1 << 20)) == n


def _tiny_fit(num_batches, callback=None, **fit_kw):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    it = mx.io.NDArrayIter(
        rng.rand(8 * num_batches, 6).astype(np.float32),
        rng.randint(0, 4, (8 * num_batches,)).astype(np.float32),
        batch_size=8)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer_params={"learning_rate": 0.1},
            batch_end_callback=callback, **fit_kw)
    return mod


def test_fit_leaves_its_phases_under_the_batch_span_under_a_profile(
        tmp_path):
    _tiny_fit(2)
    assert tracing.spans_recent() == []     # nothing on: nothing recorded
    with _Profile(tmp_path) as prof:
        _tiny_fit(6, callback=mx.callback.Speedometer(8, 3))
    spans = tracing.spans_recent(1 << 20)
    nest = _children(spans)
    assert nest[(None, "fit.batch")] == 6
    assert nest[(None, "fit.data")] == 7    # the last finds the end
    for phase in ("fit.forward_backward", "fit.update", "fit.metric",
                  "fit.callbacks"):
        assert nest[("fit.batch", phase)] == 6
    # Speedometer reads the metric every third batch, inside its phase
    assert nest[("fit.callbacks", "fit.sync")] >= 1
    batches = [r for r in spans if r["name"] == "fit.batch"]
    assert [r["attrs"]["nbatch"] for r in batches] == list(range(6))
    assert {r["tid"] for r in spans} == {threading.get_native_id()}
    events = prof.events()
    for name in ("fit.batch", "fit.data", "fit.forward_backward",
                 "fit.update", "fit.metric", "fit.callbacks", "fit.sync"):
        assert "mx." + name in events, name
    assert events["mx.fit.batch"][0][1]["epoch"] == 0


def test_a_fit_that_raises_leaves_no_span_open():
    tracing.enable()

    def boom(param):
        if param.nbatch == 1:
            raise RuntimeError("callback")

    with pytest.raises(RuntimeError):
        _tiny_fit(4, callback=boom)
    assert tracing.current() is None
    status = [r["status"] for r in tracing.spans_recent()
              if r["name"] == "fit.batch"]
    assert status == ["ok", "error"]


def test_host_reads_are_spans_where_the_program_blocks():
    tracing.enable()
    a = mx.nd.ones((2, 2))
    a.asnumpy()
    a.wait_to_read()
    mx.nd.ones((1,)).asscalar()
    sites = [r["attrs"]["site"] for r in tracing.spans_recent()
             if r["name"] == "host_read"]
    assert sites == ["asnumpy", "wait_to_read", "asnumpy"]


# -- set-up's loads ------------------------------------------------------------

def test_compile_cache_keeps_each_program_with_its_moment():
    before = compile_cache.programs()
    base = compile_cache.stats()["program_seconds"]
    t_lo = time.monotonic()
    jax.jit(lambda x: x * 3 + len(before))(np.ones(3, np.float32))
    t_hi = time.monotonic()
    new = compile_cache.programs()[len(before):]
    assert new, "the compile was not heard"
    for at, seconds, hit in new:
        assert t_lo <= at <= t_hi and 0 < seconds < t_hi - t_lo + 1e-3
        assert hit is False             # the suite keeps the cache off
    assert compile_cache.stats()["program_seconds"] == pytest.approx(
        base + sum(s for _at, s, _hit in new), abs=1e-4)
