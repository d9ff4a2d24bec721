"""Telemetry subsystem (docs/observability.md): registry semantics,
phase timers, exporters, transport counters, the recompile detector,
``Module.fit`` integration (all five instrument families), and the
disabled-overhead guarantee."""

import json
import logging
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(autouse=True)
def _clean_registry():
    """Fresh, enabled registry per test; disabled again afterwards so
    telemetry never leaks into the rest of the suite."""
    telemetry.reset()
    telemetry.enable()
    yield
    telemetry.disable()
    telemetry.reset()


class _Param:
    def __init__(self, epoch=0, nbatch=0, eval_metric=None):
        self.epoch = epoch
        self.nbatch = nbatch
        self.eval_metric = eval_metric


# -- registry semantics -----------------------------------------------------

def test_counters_accumulate_per_label_set():
    telemetry.inc("t.c")
    telemetry.inc("t.c", 2)
    telemetry.inc("t.c", 5, server=1)
    snap = telemetry.snapshot()
    assert snap["counters"]["t.c"][""] == 3
    assert snap["counters"]["t.c"]["server=1"] == 5
    assert telemetry.counter_total("t.c") == 8


def test_counter_declare_at_zero():
    telemetry.inc("t.zero", 0)
    assert telemetry.snapshot()["counters"]["t.zero"][""] == 0


def test_gauge_last_write_wins():
    telemetry.set_gauge("t.g", 1)
    telemetry.set_gauge("t.g", 42.5)
    assert telemetry.gauge_value("t.g") == 42.5


def test_hist_quantile_estimates_from_buckets():
    for v in (0.001,) * 50 + (0.08,) * 49 + (2.0,):
        telemetry.observe("t.lat", v, buckets=(0.005, 0.01, 0.05, 0.1, 1.0))
    # p50 falls in the first bucket, p99 in the (0.05, 0.1] bucket, and
    # p100 caps at the observed max rather than the +Inf bound
    assert telemetry.hist_quantile("t.lat", 0.5) <= 0.005
    assert 0.05 <= telemetry.hist_quantile("t.lat", 0.99) <= 0.1
    assert telemetry.hist_quantile("t.lat", 1.0) == 2.0
    assert telemetry.hist_quantile("t.absent", 0.5) is None


def test_histogram_stats_and_buckets():
    for v in (0.002, 0.003, 2.0):
        telemetry.observe("t.h", v)
    h = telemetry.snapshot()["histograms"]["t.h"][""]
    assert h["count"] == 3
    assert h["min"] == 0.002 and h["max"] == 2.0
    assert abs(h["sum"] - 2.005) < 1e-9
    # buckets are cumulative (Prometheus le semantics)
    assert h["buckets"]["0.01"] == 2
    assert h["buckets"]["10"] == 3
    assert h["buckets"]["+Inf"] == 3


def test_disabled_is_noop():
    telemetry.disable()
    telemetry.inc("t.off")
    telemetry.set_gauge("t.off.g", 1)
    telemetry.observe("t.off.h", 1)
    telemetry.event("t.off.e")
    snap = telemetry.snapshot()
    assert not telemetry.enabled()
    assert snap["counters"] == {} and snap["gauges"] == {}
    assert snap["histograms"] == {} and snap["events"]["count"] == 0


def test_events_ring_and_jsonl(tmp_path):
    telemetry.event("shard_lost", rank=3)
    telemetry.event("rejoined", rank=3)
    path = str(tmp_path / "events.jsonl")
    telemetry.dump_events(path)
    with open(path) as f:
        lines = [json.loads(ln) for ln in f]
    assert [ln["event"] for ln in lines] == ["shard_lost", "rejoined"]
    assert lines[0]["rank"] == 3 and "ts" in lines[0]


def test_dump_snapshot_json(tmp_path):
    telemetry.inc("t.c", 7)
    path = str(tmp_path / "snap.json")
    telemetry.dump(path)
    with open(path) as f:
        snap = json.load(f)
    assert snap["counters"]["t.c"][""] == 7
    assert set(snap) >= {"enabled", "counters", "gauges", "histograms",
                         "events"}


def test_dump_env_var_writes_at_exit(tmp_path):
    """MXNET_TELEMETRY_DUMP implies enablement and atexit-dumps snapshot
    JSON + events JSONL."""
    out = tmp_path / "tele.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_TELEMETRY_DUMP=str(out))
    env.pop("MXNET_TELEMETRY", None)
    code = ("import mxnet_tpu as mx\n"
            "assert mx.telemetry.enabled()\n"
            "mx.telemetry.inc('sub.proc', 2)\n"
            "mx.telemetry.event('sub_event', k='v')\n")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, cwd=ROOT,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    with open(out) as f:
        assert json.load(f)["counters"]["sub.proc"][""] == 2
    with open(tmp_path / "tele.events.jsonl") as f:
        assert json.loads(f.readline())["event"] == "sub_event"


# -- Prometheus exposition --------------------------------------------------

def test_prometheus_text_format():
    telemetry.inc("t.req", 3, route='a"b')
    telemetry.set_gauge("t.depth", 2.5)
    telemetry.observe("t.lat", 0.003)
    text = telemetry.prometheus_text()
    assert "# TYPE mxnet_t_req counter" in text
    assert 'mxnet_t_req{route="a\\"b"} 3' in text
    assert "# TYPE mxnet_t_depth gauge" in text
    assert "mxnet_t_depth 2.5" in text
    assert "# TYPE mxnet_t_lat histogram" in text
    # cumulative buckets, +Inf, sum and count
    assert 'mxnet_t_lat_bucket{le="0.01"} 1' in text
    assert 'mxnet_t_lat_bucket{le="+Inf"} 1' in text
    assert "mxnet_t_lat_sum 0.003" in text
    assert "mxnet_t_lat_count 1" in text


def test_write_prometheus(tmp_path):
    telemetry.inc("t.c", 1)
    path = str(tmp_path / "metrics.prom")
    telemetry.write_prometheus(path)
    with open(path) as f:
        assert "mxnet_t_c 1" in f.read()


# -- phase timers -----------------------------------------------------------

def test_phase_records_histogram():
    with telemetry.phase("data"):
        time.sleep(0.002)
    totals = telemetry.phase_totals("fit")
    assert totals["data"][1] == 1
    assert totals["data"][0] >= 0.002


def test_phase_disabled_no_clock():
    telemetry.disable()
    with telemetry.phase("data") as p:
        pass
    assert not hasattr(p, "_t0") or p._on is False
    assert telemetry.phase_totals("fit") == {}


def test_phase_emits_chrome_span_when_profiling(tmp_path):
    from mxnet_tpu import profiler

    profiler.profiler_set_config(mode="symbolic",
                                 filename=str(tmp_path / "prof.json"))
    profiler.profiler_set_state("run")
    try:
        with telemetry.phase("data"):
            pass
    finally:
        profiler.profiler_set_state("stop")
    fname = profiler.dump_profile()
    profiler.profiler_set_config()  # restore defaults for later tests
    with open(fname) as f:
        names = [e["name"] for e in json.load(f)["traceEvents"]]
    assert "fit:data" in names


# -- transport / retry counters ---------------------------------------------

def test_local_kvstore_transport_counters():
    kv = mx.kv.create("local")
    kv.init(3, mx.nd.ones((4, 2)))
    kv.push(3, mx.nd.ones((4, 2)))
    out = mx.nd.zeros((4, 2))
    kv.pull(3, out=out)
    snap = telemetry.snapshot()["counters"]
    assert snap["kvstore.push.count"]["store=local"] == 1
    assert snap["kvstore.push.bytes"]["store=local"] == 4 * 2 * 4
    assert snap["kvstore.pull.count"]["store=local"] == 1
    assert snap["kvstore.pull.bytes"]["store=local"] == 4 * 2 * 4


def test_retry_call_metric_counters():
    from mxnet_tpu.retry import retry_call

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    assert retry_call(flaky, retry_on=(OSError,), deadline=30,
                      base_delay=0.001, metric="test.site") == "ok"
    snap = telemetry.snapshot()["counters"]
    assert snap["retry.count"]["site=test.site"] == 2
    assert snap["retry.backoff_seconds"]["site=test.site"] > 0


def test_fault_injection_counted():
    from mxnet_tpu import faults

    faults.arm("recordio.read", at=1)
    try:
        assert faults.should_fire("recordio.read")
    finally:
        faults.disarm()
    snap = telemetry.snapshot()["counters"]
    assert snap["resilience.fault_injected"]["point=recordio.read"] == 1
    events = telemetry.snapshot()["events"]["recent"]
    assert any(e["event"] == "fault_injected" for e in events)


# -- compile tracking / recompile detector ----------------------------------

def _small_exec():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=2, name="fct")
    return net.simple_bind(mx.cpu(), data=(2, 3))


def test_compile_count_and_fn_cache_hits():
    ex = _small_exec()
    ex.forward(is_train=False)
    ex.forward(is_train=False)
    assert telemetry.counter_total("xla.compile.count") == 1
    # the in-process jit function cache, split from the persistent
    # on-disk cache counters (xla.compile.persistent_cache_*)
    assert telemetry.counter_total("xla.compile.fn_cache_hits") >= 1
    assert telemetry.counter_total("xla.compile.seconds") > 0


def test_get_fn_cache_key_reads_the_mirror_mode_and_nothing_else(
        monkeypatch):
    """What a bound executor's function cache is keyed on: its kind and the
    mirror mode, the one knob a trace depends on.  A look-up that hits
    reads that one variable of the environment; a second call of the same
    kind is a hit and hands back the same function."""
    import os

    ex = _small_exec()
    fn = ex._get_fn("train")
    hits = telemetry.counter_total("xla.compile.fn_cache_hits")
    read = []

    class Recording(dict):
        def get(self, key, default=None):
            read.append(key)
            return super().get(key, default)

        def __getitem__(self, key):
            read.append(key)
            return super().__getitem__(key)

    monkeypatch.setattr(os, "environ", Recording(os.environ))
    assert ex._get_fn("train") is fn
    monkeypatch.undo()
    assert read == ["MXNET_BACKWARD_DO_MIRROR"]
    assert telemetry.counter_total("xla.compile.fn_cache_hits") == hits + 1


def test_recompile_detector_warns_on_same_program_rebuild(monkeypatch,
                                                          caplog):
    monkeypatch.setenv("MXNET_RECOMPILE_WARN_THRESHOLD", "1")
    ex = _small_exec()
    with caplog.at_level(logging.WARNING):
        ex._get_fn("predict")
        # an env-fingerprint flip retraces the SAME program identity —
        # the recompilation-churn signature
        monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
        ex._get_fn("predict")
    assert "recompilation churn" in caplog.text
    assert telemetry.counter_total("xla.recompile_warnings") >= 1


def test_recompile_detector_ignores_first_builds(monkeypatch, caplog):
    """Distinct programs each compiling once is normal operation, not
    churn — must stay silent even at threshold 1."""
    monkeypatch.setenv("MXNET_RECOMPILE_WARN_THRESHOLD", "1")
    ex = _small_exec()
    with caplog.at_level(logging.WARNING):
        ex._get_fn("predict")
        ex._get_fn("train_fwd")
        ex._get_fn("train")
    assert "recompilation churn" not in caplog.text


def test_recompile_detector_disabled_at_zero(monkeypatch, caplog):
    monkeypatch.setenv("MXNET_RECOMPILE_WARN_THRESHOLD", "0")
    ex = _small_exec()
    with caplog.at_level(logging.WARNING):
        ex._get_fn("predict")
        monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
        ex._get_fn("predict")
    assert "recompilation churn" not in caplog.text


# -- memory gauges ----------------------------------------------------------

def test_sample_memory_host_gauge():
    telemetry.sample_memory()
    gauges = telemetry.snapshot()["gauges"]
    assert any(name.startswith("memory.") for name in gauges)


# -- Module.fit integration (the acceptance check) --------------------------

def _fit_small(num_epoch=2, **fit_kwargs):
    rs = np.random.RandomState(0)
    x = rs.rand(64, 10).astype(np.float32)
    y = (x.sum(axis=1) > 5).astype(np.float32)
    train = mx.io.NDArrayIter(x, y, batch_size=16)
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=2, name="fc")
    net = mx.sym.SoftmaxOutput(fc, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(train, num_epoch=num_epoch, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1}, **fit_kwargs)
    return mod


def test_fit_snapshot_contains_all_five_families():
    """ISSUE 2 acceptance: after a small fit, snapshot() carries fit
    phases, kvstore transport, compile, resilience and memory."""
    _fit_small(kvstore=mx.kv.create("local"))
    snap = telemetry.snapshot()
    counters, gauges = snap["counters"], snap["gauges"]
    hists = snap["histograms"]
    # 1. fit phases
    phases = {lbl.split("=", 1)[1]
              for lbl in hists["fit.phase_seconds"]}
    assert {"data", "forward_backward", "update", "metric"} <= phases
    assert counters["fit.batches"][""] == 2 * 4  # 2 epochs x 64/16
    assert counters["fit.epochs"][""] == 2
    # 2. kvstore transport
    assert counters["kvstore.push.count"]["store=local"] > 0
    assert counters["kvstore.pull.count"]["store=local"] > 0
    # 3. compile tracking
    assert counters["xla.compile.count"] and \
        telemetry.counter_total("xla.compile.seconds") > 0
    # 4. resilience events (declared at zero on a clean run)
    assert counters["resilience.nan_batches"][""] == 0
    assert counters["resilience.checkpoint.saves"][""] == 0
    # 5. memory gauges
    assert any(name.startswith("memory.") for name in gauges)


def test_fit_checkpoint_phase_and_counter(tmp_path):
    prefix = str(tmp_path / "ck")
    _fit_small(num_epoch=1, checkpoint_prefix=prefix)
    snap = telemetry.snapshot()
    assert snap["counters"]["resilience.checkpoint.saves"][""] == 1
    assert "phase=checkpoint" in snap["histograms"]["fit.phase_seconds"]


# -- Speedometer gauges / TelemetryReport -----------------------------------

def test_speedometer_feeds_throughput_gauges():
    sp = mx.callback.Speedometer(batch_size=4, frequent=1, smoothing=0.5)
    sp(_Param(nbatch=0))  # arms the mark
    time.sleep(0.002)
    sp(_Param(nbatch=1))
    time.sleep(0.002)
    sp(_Param(nbatch=2))
    inst = telemetry.gauge_value("fit.samples_per_sec", kind="instant")
    ema = telemetry.gauge_value("fit.samples_per_sec", kind="smoothed")
    assert inst is not None and inst > 0
    assert ema is not None and ema > 0
    assert sp._ema is not None


def test_telemetry_report_logs_phase_deltas(caplog):
    telemetry.observe("fit.phase_seconds", 0.01, phase="data")
    telemetry.observe("fit.phase_seconds", 0.05, phase="forward_backward")
    telemetry.inc("kvstore.push.count", 5)
    report = mx.callback.TelemetryReport(frequent=2)
    with caplog.at_level(logging.INFO):
        report(_Param(nbatch=2))
        report.epoch(0)
    assert "phases/batch" in caplog.text
    assert "forward_backward" in caplog.text
    assert "telemetry:" in caplog.text


def test_telemetry_report_noop_when_disabled(caplog):
    telemetry.disable()
    report = mx.callback.TelemetryReport(frequent=1)
    with caplog.at_level(logging.INFO):
        report(_Param(nbatch=1))
    assert "telemetry is disabled" in caplog.text


# -- the <1% overhead guarantee ---------------------------------------------

def test_disabled_overhead_is_negligible():
    """With telemetry off (the default), the per-batch instrumentation in
    the fit loop (4 phase timers + a counter bump) must cost well under
    1% of any real training step; 50us/batch against >=5ms steps."""
    telemetry.disable()
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        with telemetry.phase("data"):
            pass
        with telemetry.phase("forward_backward"):
            pass
        with telemetry.phase("update"):
            pass
        with telemetry.phase("metric"):
            pass
        telemetry.inc("fit.batches")
    per_batch = (time.perf_counter() - t0) / n
    assert per_batch < 50e-6, "disabled telemetry costs %.1fus/batch" \
        % (per_batch * 1e6)


# -- fleet export & aggregation (ISSUE 17) ----------------------------------

def _publish(tmp_path, proc, fill):
    """Record ``fill()`` into a fresh registry and publish it as
    ``<proc>.telemetry.json`` — one simulated fleet member."""
    telemetry.reset()
    fill()
    snap = dict(telemetry.snapshot(), proc=proc, pid=os.getpid(),
                export_ts=round(time.time(), 6))
    path = tmp_path / ("%s.telemetry.json" % proc)
    path.write_text(json.dumps(snap, default=str))
    telemetry.reset()
    return snap


def test_exporter_reset_audit(tmp_path):
    """Satellite 2: a ``reset()`` under an armed exporter neither kills
    the cadence thread nor resurrects stale counters in the next
    publish, and declared families stay visible at zero."""
    telemetry.inc("resilience.rollbacks", 0)  # declared at zero
    telemetry.inc("kvstore.push.count", 7, store="local")
    exp = telemetry.start_exporter(str(tmp_path), interval_s=0.05,
                                   proc="w0")
    try:
        assert telemetry.exporter_running()
        path = tmp_path / "w0.telemetry.json"
        assert path.exists(), "first snapshot publishes immediately"
        first = json.loads(path.read_text())
        assert first["proc"] == "w0" and first["pid"] == os.getpid()
        assert first["counters"]["kvstore.push.count"]["store=local"] \
            == 7

        telemetry.reset()
        # the audit: exporter survives the reset...
        assert telemetry.exporter_running()
        snap = telemetry.snapshot()
        # ...declared families are re-seeded at zero, not dropped...
        assert snap["counters"]["resilience.rollbacks"][""] == 0
        # ...and the NEXT publish carries no stale pre-reset totals
        deadline = time.monotonic() + 10
        while True:
            cur = json.loads(path.read_text())
            if cur["export_ts"] > first["export_ts"]:
                break
            assert time.monotonic() < deadline
            time.sleep(0.02)
        assert "kvstore.push.count" not in cur["counters"]
        assert cur["counters"]["resilience.rollbacks"][""] == 0

        # idempotent arming: no second thread stacks up
        assert telemetry.start_exporter(str(tmp_path)) is exp
    finally:
        telemetry.stop_exporter()
    assert not telemetry.exporter_running()


def test_aggregate_merges_a_three_process_fleet(tmp_path):
    """ISSUE 17 acceptance: counter totals equal the sum over dumps,
    gauges keep per-proc rows, and quantiles come from MERGED
    buckets."""
    lat = [0.004, 0.009, 0.030, 0.070, 0.200, 0.450]

    def fill(k):
        def _f():
            telemetry.inc("fit.batches", 10 * (k + 1))
            telemetry.inc("serving.request.count", k + 1, model="m")
            telemetry.set_gauge("serving.queue.depth", float(k),
                                model="m")
            for v in lat[2 * k:2 * k + 2]:
                telemetry.observe("serving.request.latency_seconds", v)
        return _f

    snaps = [_publish(tmp_path, "w%d" % k, fill(k)) for k in range(3)]
    agg = telemetry.aggregate(str(tmp_path))
    assert agg["procs"] == ["w0", "w1", "w2"]
    # counters: fleet totals are the exact sum of the dumps
    assert agg["counters"]["fit.batches"][""] == 10 + 20 + 30
    assert agg["counters"]["serving.request.count"]["model=m"] == 6
    for snap in snaps:
        assert snap["counters"]["fit.batches"][""] in (10, 20, 30)
    # gauges: one row per proc, never summed
    g = agg["gauges"]["serving.queue.depth"]
    assert g == {"model=m,proc=w0": 0.0, "model=m,proc=w1": 1.0,
                 "model=m,proc=w2": 2.0}
    # histograms: merged bucket-wise; count/sum are fleet-wide and the
    # p50 estimate falls inside the observed range
    h = agg["histograms"]["serving.request.latency_seconds"][""]
    assert h["count"] == 6
    assert abs(h["sum"] - sum(lat)) < 1e-9
    assert h["min"] == min(lat) and h["max"] == max(lat)
    bounds, counts = [], []
    prev = 0
    for b, c in sorted(h["buckets"].items(),
                       key=lambda kv: float("inf") if kv[0] == "+Inf"
                       else float(kv[0])):
        bounds.append(float("inf") if b == "+Inf" else float(b))
        counts.append(c - prev)
        prev = c
    assert prev == 6, "cumulative +Inf bucket holds every observation"
    q50 = telemetry.quantile_from_counts(
        [b for b in bounds if b != float("inf")], counts, 0.5,
        lo=h["min"], hi=h["max"])
    assert min(lat) <= q50 <= max(lat)
    # a torn file loses one cadence, not the merge
    (tmp_path / "torn.telemetry.json").write_text("{not json")
    again = telemetry.aggregate(str(tmp_path))
    assert again["counters"]["fit.batches"][""] == 60


def test_prometheus_text_of_aggregate_is_strictly_well_formed(tmp_path):
    """Satellite 3: every line of ``prometheus_text(aggregate(...))``
    passes a strict exposition-format check — TYPE comments, metric
    and label name charsets, parseable values, cumulative ascending
    ``le`` buckets with ``+Inf`` == ``_count``."""
    def fill(k):
        def _f():
            telemetry.inc("serving.request.count", k + 1, model="m")
            telemetry.set_gauge("serving.queue.depth", k, model="m")
            telemetry.observe("serving.request.latency_seconds",
                              0.01 * (k + 1))
        return _f

    for k in range(2):
        _publish(tmp_path, "w%d" % k, fill(k))
    text = telemetry.prometheus_text(telemetry.aggregate(str(tmp_path)))
    assert text.endswith("\n")
    import re
    name_re = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
    label_re = r'[a-zA-Z_][a-zA-Z0-9_]*="[^"\\]*"'
    sample_re = re.compile(r"^(%s)(\{%s(,%s)*\})? (-?\d+(\.\d+)?([eE][+-]?\d+)?|\+Inf|-Inf|NaN)$"
                           % (name_re, label_re, label_re))
    type_re = re.compile(r"^# TYPE (%s) (counter|gauge|histogram)$"
                         % name_re)
    typed = {}
    samples = []
    for line in text.splitlines():
        m = type_re.match(line)
        if m:
            assert m.group(1) not in typed, "one TYPE line per family"
            typed[m.group(1)] = m.group(2)
            continue
        m = sample_re.match(line)
        assert m, "malformed exposition line: %r" % line
        samples.append(line)
        base = re.sub(r"_(bucket|sum|count)$", "", m.group(1)) \
            if m.group(1).endswith(("_bucket", "_sum", "_count")) \
            else m.group(1)
        assert m.group(1) in typed or base in typed, \
            "sample %r precedes its TYPE" % line
    # histogram series: le buckets cumulative ascending, +Inf == count
    hist = [t for t, kind in typed.items() if kind == "histogram"]
    assert hist, "the fixture recorded a histogram"
    for fam in hist:
        buckets = [s for s in samples
                   if s.startswith(fam + "_bucket")]
        assert buckets
        values = [int(s.rsplit(" ", 1)[1]) for s in buckets]
        assert values == sorted(values), "le buckets are cumulative"
        assert 'le="+Inf"' in buckets[-1]
        count = next(int(s.rsplit(" ", 1)[1]) for s in samples
                     if s.startswith(fam + "_count"))
        assert values[-1] == count
    # counters carry fleet sums; gauges carry proc= labels
    assert 'serving_request_count{model="m"} 3' in text
    assert 'proc="w0"' in text and 'proc="w1"' in text


def test_graftop_renders_the_fleet(tmp_path):
    """tools/graftop.py --once over an export dir: proc table, summed
    counters, merged-bucket latencies, per-proc gauges."""
    from tools import graftop

    def fill(k):
        def _f():
            telemetry.inc("serving.decode.tokens.count", 100 * (k + 1))
            telemetry.set_gauge("serving.decode.slot_occupancy",
                                0.25 * (k + 1), model="lm")
            telemetry.observe("serving.decode.ttft_seconds",
                              0.02 * (k + 1), model="lm")
            telemetry.event("serving.model.load", model="lm", rep=k)
        return _f

    for k in range(2):
        _publish(tmp_path, "w%d" % k, fill(k))
    frame = graftop.render(str(tmp_path))
    assert "2 proc(s)" in frame
    assert "w0" in frame and "w1" in frame
    assert "serving.decode.tokens.count" in frame
    line = next(ln for ln in frame.splitlines()
                if "serving.decode.tokens.count" in ln)
    assert line.rstrip().endswith("300"), line
    assert "LATENCIES" in frame and "serving.decode.ttft_seconds" in frame
    assert "proc=w0" in frame and "proc=w1" in frame
    assert "RECENT EVENTS" in frame and "serving.model.load" in frame
    # --once prints one frame and exits 0
    import contextlib
    import io
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = graftop.main(["--dir", str(tmp_path), "--once"])
    assert rc == 0 and "graftop" in buf.getvalue()


def test_aggregate_include_local_never_double_counts_this_process(
        tmp_path):
    """An armed exporter's own file sits in the export dir; a merge
    with ``include_local`` must read this process from its LIVE
    registry only — not once from the file and once live."""
    _publish(tmp_path, "other", lambda: telemetry.inc("fit.batches", 5))
    telemetry.inc("fit.batches", 3)
    telemetry.start_exporter(str(tmp_path), interval_s=30.0, proc="me")
    try:
        agg = telemetry.aggregate(str(tmp_path), include_local=True)
        assert agg["procs"].count("me") == 1
        assert agg["counters"]["fit.batches"][""] == 5 + 3
    finally:
        telemetry.stop_exporter()
