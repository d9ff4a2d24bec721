"""Performance attribution layer (docs/observability.md "Performance
attribution" / "Flight recorder"): executable cost/memory capture for
every executor kind ``Module.fit`` and ``Predictor`` use, HLO
fingerprint stability across identical runs (and change detection
across different ones), flight-recorder dumps on NaN trip / preemption
/ crash / serving drain, the live MFU gauge, the checkpoint queue-wait
histogram, and the serving trace spans."""

import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import checkpoint as ckpt
from mxnet_tpu import faults, perfdebug, telemetry

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(autouse=True)
def _clean_perfdebug():
    """Attribution + telemetry enabled and empty per test; everything
    disabled again afterwards so nothing leaks into the suite."""
    telemetry.reset()
    telemetry.enable()
    perfdebug.reset()
    perfdebug.enable()
    perfdebug._flight_flag = None  # tri-state: follow the env again
    yield
    perfdebug._enabled_flag = None
    perfdebug._flight_flag = None
    perfdebug.reset()
    telemetry.disable()
    telemetry.reset()


def _mlp():
    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    h = mx.sym.Activation(h, act_type="relu", name="relu1")
    out = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(h, num_hidden=4, name="fc2"), name="softmax")
    return out


def _train_iter(n=32, batch=8, in_dim=16, classes=4, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.rand(n, in_dim).astype(np.float32)
    y = rs.randint(0, classes, n).astype(np.float32)
    return mx.io.NDArrayIter(x, y, batch_size=batch,
                             last_batch_handle="discard")


def _fit(sym, **kw):
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.fit(_train_iter(), num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.01}, **kw)
    return mod


# -- cost / memory capture --------------------------------------------------

def test_capture_covers_fit_and_predictor_kinds(tmp_path):
    sym = _mlp()
    mod = _fit(sym, eval_data=_train_iter(seed=1))
    # Predictor traffic (the serving surface) through the same symbol
    arg, aux = mod.get_params()
    params = {("arg:%s" % k): v.asnumpy() for k, v in arg.items()}
    params.update({("aux:%s" % k): v.asnumpy() for k, v in aux.items()})
    import io as _io

    buf = _io.BytesIO()
    np.savez(buf, **params)
    pred = mx.predict.Predictor(sym.tojson(), buf.getvalue(),
                                {"data": (4, 16)})
    pred.set_input("data", np.zeros((4, 16), np.float32))
    pred.forward()
    rows = perfdebug.report()
    kinds = {r["kind"] for r in rows}
    # fit compiles the train step; fit's eval pass and the Predictor
    # both compile predict executables (distinct shape signatures)
    assert "train" in kinds and "predict" in kinds
    for r in rows:
        assert r["fingerprint"] and len(r["fingerprint"]) == 16
        assert r["flops"] and r["flops"] > 0
        assert r["bytes_accessed"] and r["bytes_accessed"] > 0
        # the HBM breakdown: argument/output/temp bytes from XLA
        # memory_analysis (generated-code may legitimately be 0 on CPU)
        for key in ("argument_bytes", "output_bytes", "temp_bytes"):
            assert key in r["hbm"], r
        assert r["hbm"]["argument_bytes"] > 0
    # the predictor's batch-4 predict is a different signature than
    # fit's eval batch-8 predict
    predict_sigs = {r["shapes"] for r in rows if r["kind"] == "predict"}
    assert len(predict_sigs) == 2
    # executable gauges + the HBM watermark landed in telemetry
    assert telemetry.gauge_value("perf.executable.flops", exec="softmax",
                                 kind="train") > 0
    assert telemetry.gauge_value("perf.hbm_peak_bytes") > 0
    # report_text renders every row
    txt = perfdebug.report_text()
    assert "train" in txt and "predict" in txt


def test_fused_and_bulk_kinds_captured(monkeypatch):
    monkeypatch.setenv("MXNET_FUSE_TRAIN_STEP", "1")
    sym = _mlp()
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 16))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd")
    rs = np.random.RandomState(0)
    b = mx.io.DataBatch(
        data=[mx.nd.array(rs.rand(8, 16).astype(np.float32))],
        label=[mx.nd.array(rs.randint(0, 4, 8).astype(np.float32))])
    mod.forward_backward(b)
    mod.update()                      # single-dispatch fused step
    mod.run_bulk([b, b])              # scan over 2 steps
    kinds = {r["kind"] for r in perfdebug.report()}
    assert "train_sgd" in kinds
    assert "train_sgd_scan" in kinds


# -- fingerprint stability / change detection -------------------------------

def test_fingerprints_stable_across_identical_fits():
    sym = _mlp()
    _fit(sym)
    first = perfdebug.fingerprints()
    assert first
    # a second, identically-shaped fit on a FRESH module re-traces and
    # re-captures every executable: zero spurious changes
    _fit(sym)
    assert perfdebug.fingerprints() == first
    assert perfdebug.changes() == []
    # every entry records the re-build
    assert all(r["builds"] == 2 for r in perfdebug.report()
               if r["kind"] == "train")


def test_fingerprints_ignore_parameter_naming():
    # parameter names are baked into the lowered text as
    # jax.result_info/arg_info annotations; the normalized fingerprint
    # must hash two identically-structured networks that differ ONLY in
    # layer names to the same value.  (An anonymous rebuild can
    # legitimately change the fingerprint: auto-name counters crossing
    # a digit boundary reorder the gradient pytree's sorted keys, which
    # permutes real HLO arguments — different program, different hash.)
    def build(tag):
        data = mx.sym.Variable("data")
        h = mx.sym.FullyConnected(data, num_hidden=16,
                                  name="%s_hid" % tag)
        out = mx.sym.SoftmaxOutput(
            mx.sym.FullyConnected(h, num_hidden=4, name="%s_out" % tag),
            name="softmax")
        return out

    _fit(build("alpha"))
    first = perfdebug.fingerprints()
    perfdebug.reset()
    _fit(build("bravo"))
    assert perfdebug.fingerprints() == first
    assert perfdebug.changes() == []


def test_fingerprint_change_detected_and_counted():
    import jax.numpy as jnp
    import jax

    a = np.zeros((4, 4), np.float32)
    f1 = jax.jit(lambda x: x + 1)
    f2 = jax.jit(lambda x: x * 3 + 2)
    perfdebug.capture("demo", "predict", f1.lower, (a,))
    assert perfdebug.changes() == []
    perfdebug.capture("demo", "predict", f2.lower, (a,))
    chg = perfdebug.changes()
    assert len(chg) == 1
    assert chg[0]["exec"] == "demo" and chg[0]["old"] != chg[0]["new"]
    assert telemetry.counter_total("perf.fingerprint_changes") == 1
    assert any(e["event"] == "hlo.fingerprint_change"
               for e in telemetry.events_recent())


def test_save_and_diff_fingerprints(tmp_path):
    import jax

    a = np.zeros((2, 2), np.float32)
    jax_fn = jax.jit(lambda x: x + 1)
    perfdebug.capture("m1", "predict", jax_fn.lower, (a,))
    path = str(tmp_path / "fp.json")
    perfdebug.save_fingerprints(path)
    # same state: no diff
    d = perfdebug.diff_fingerprints(path)
    assert d == {"changed": {}, "added": [], "removed": []}
    # a new executable appears
    perfdebug.capture("m2", "predict", jax.jit(lambda x: x - 1).lower,
                      (a,))
    d = perfdebug.diff_fingerprints(path)
    assert d["added"] == ["m2/predict@%s"
                          % perfdebug.report()[1]["shapes"]]


def test_disabled_capture_is_inert():
    perfdebug.disable()
    _fit(_mlp())
    assert perfdebug.report() == []
    assert perfdebug.report_text().startswith("perfdebug: no executables")


# -- live MFU ---------------------------------------------------------------

def test_mfu_gauge_from_speedometer(monkeypatch):
    import jax

    monkeypatch.setitem(perfdebug.PEAK_TFLOPS_BY_KIND,
                        jax.local_devices()[0].device_kind, 100.0)
    _fit(_mlp())
    flops = perfdebug.step_flops()
    assert flops and flops > 0
    mfu = perfdebug.note_throughput(1e6, 8)  # 1M samples/sec, batch 8
    expected = 100.0 * (1e6 * flops / 8 / 1e12) / 100.0
    assert mfu == pytest.approx(expected)
    assert telemetry.gauge_value("perf.mfu_pct") == pytest.approx(mfu)
    # the Speedometer path reads the same machinery at its log cadence
    speedo = mx.callback.Speedometer(batch_size=8, frequent=2)

    class P:
        epoch, nbatch, eval_metric = 0, 0, None

    speedo(P())        # arms the mark
    P.nbatch = 2
    speedo(P())        # logs -> sets perf.mfu_pct
    assert telemetry.gauge_value("perf.mfu_pct") is not None


def test_mfu_none_without_peak():
    import jax

    _fit(_mlp())
    # CPU device_kind is not in the peak table -> the live gauge stays
    # silent, while the measurement-path lookup refuses to guess
    assert perfdebug.note_throughput(1e6, 8) is None
    with pytest.raises(KeyError, match="no published bf16 peak"):
        perfdebug.device_peak_tflops(jax.local_devices()[0])


# -- flight recorder --------------------------------------------------------

def test_flight_dump_on_nan_trip(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_FLIGHT_RECORDER_DIR", str(tmp_path))
    faults.arm("fit.batch", at=2)
    try:
        _fit(_mlp(), nan_policy="skip_batch")
    finally:
        faults.disarm()
    dumps = glob.glob(str(tmp_path / "flightrec-*-nan_trip.json"))
    assert len(dumps) == 1
    payload = json.load(open(dumps[0]))
    assert payload["reason"] == "nan_trip"
    assert payload["detail"]["action"] == "skip_batch"
    assert any(e["event"] == "nan_batch" for e in payload["events"])
    # per-batch phase timings rode the ring into the dump
    assert any(r["kind"] == "phase" and r["family"] == "fit"
               for r in payload["records"])


def test_flight_dump_on_preemption_fault(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_FLIGHT_RECORDER_DIR", str(tmp_path))
    faults.arm("fit.preempt", at=2)
    try:
        with pytest.raises(ckpt.TrainingPreempted) as ei:
            _fit(_mlp(), checkpoint_prefix=str(tmp_path / "ck"))
    finally:
        faults.disarm()
    dumps = glob.glob(str(tmp_path / "flightrec-*-preemption.json"))
    assert len(dumps) == 1
    payload = json.load(open(dumps[0]))
    # the acceptance demo: the dump carries the last-batch phase
    # timings AND the preemption event
    phases = [r for r in payload["records"]
              if r["kind"] == "phase" and r["family"] == "fit"]
    assert {p["phase"] for p in phases} >= {"data", "forward_backward",
                                            "update"}
    pre = [e for e in payload["events"] if e["event"] == "preemption"]
    assert pre and pre[0]["signal"] == 15
    assert payload["detail"]["checkpoint"] == ei.value.checkpoint_path
    # the attribution table survived into the post-mortem
    assert any(a["kind"] == "train" for a in payload["attribution"])


def test_flight_dump_on_crash(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_FLIGHT_RECORDER_DIR", str(tmp_path))
    faults.arm("fit.batch", at=1)
    try:
        with pytest.raises(mx.MXNetError):
            _fit(_mlp(), nan_policy="raise")
    finally:
        faults.disarm()
    # the raise trips BOTH the nan_trip dump and the generic crash dump
    assert glob.glob(str(tmp_path / "flightrec-*-nan_trip.json"))
    crash = glob.glob(str(tmp_path / "flightrec-*-crash.json"))
    assert len(crash) == 1
    payload = json.load(open(crash[0]))
    assert "NaN/Inf" in payload["detail"]["error"]


def test_flight_dump_on_serving_drain(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_FLIGHT_RECORDER_DIR", str(tmp_path))
    from mxnet_tpu import serving

    rs = np.random.RandomState(0)
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=4, name="fc"),
        name="softmax")
    import io as _io

    buf = _io.BytesIO()
    np.savez(buf, fc_weight=rs.randn(4, 8).astype(np.float32),
             fc_bias=np.zeros(4, np.float32))
    reg = serving.ModelRegistry()
    reg.load("m", net, buf.getvalue(), (8,), buckets=(1, 4))
    server = serving.ServingHTTPServer(reg, port=0).start()
    assert server.drain(deadline=5)
    reg.close()
    dumps = glob.glob(str(tmp_path / "flightrec-*-serving_drain.json"))
    assert len(dumps) == 1


def test_flight_recorder_disabled_no_dump(tmp_path, monkeypatch):
    monkeypatch.delenv("MXNET_FLIGHT_RECORDER_DIR", raising=False)
    monkeypatch.delenv("MXNET_FLIGHT_RECORDER", raising=False)
    assert not perfdebug.flight_enabled()
    assert perfdebug.flight_dump("manual") is None


def test_flight_ring_is_bounded(monkeypatch):
    monkeypatch.setenv("MXNET_FLIGHT_RECORDER", "1")
    monkeypatch.setenv("MXNET_FLIGHT_RECORDER_SIZE", "16")
    for i in range(100):
        perfdebug.flight_record("mark", i=i)
    with perfdebug._flight_lock:
        assert len(perfdebug._flight) == 16
        assert perfdebug._flight[-1]["i"] == 99


# -- checkpoint queue-wait histogram ----------------------------------------

def test_checkpoint_queue_wait_histogram(tmp_path):
    _fit(_mlp(), checkpoint_prefix=str(tmp_path / "ck"),
         checkpoint_every_n_batches=2)
    snap = telemetry.snapshot()
    h = snap["histograms"].get(
        "resilience.checkpoint.queue_wait_seconds", {}).get("")
    assert h and h["count"] >= 1
    assert snap["histograms"][
        "resilience.checkpoint.async_write_seconds"][""]["count"] >= 1


# -- serving trace spans ----------------------------------------------------

def test_serving_dispatch_and_http_spans(tmp_path):
    from mxnet_tpu import profiler, serving

    rs = np.random.RandomState(0)
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=4, name="fc"),
        name="softmax")
    import io as _io
    import json as _json
    import urllib.request

    buf = _io.BytesIO()
    np.savez(buf, fc_weight=rs.randn(4, 8).astype(np.float32),
             fc_bias=np.zeros(4, np.float32))
    reg = serving.ModelRegistry()
    reg.load("spanny", net, buf.getvalue(), (8,), buckets=(1, 4))
    server = serving.ServingHTTPServer(reg, port=0).start()
    profile_path = str(tmp_path / "profile.json")
    profiler.profiler_set_config(mode="all", filename=profile_path)
    profiler.profiler_set_state("run")
    try:
        body = _json.dumps({"model": "spanny",
                            "data": np.zeros((2, 8)).tolist()}).encode()
        req = urllib.request.Request(
            server.url + "/predict", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.status == 200
    finally:
        profiler.profiler_set_state("stop")
        server.stop()
        reg.close()
    profiler.dump_profile()
    events = json.load(open(profile_path))["traceEvents"]
    names = {e["name"] for e in events}
    # batcher dispatch and HTTP handling sit on the same timeline
    assert "serving:spanny:dispatch" in names
    assert "serving:http:spanny" in names


def test_flight_recorder_env_implies_telemetry(tmp_path):
    """An armed flight recorder over disabled telemetry would dump
    hollow files; arming via env at process start must enable the
    registry (same implication as MXNET_TELEMETRY_DUMP)."""
    env = dict(os.environ, MXNET_FLIGHT_RECORDER="1",
               JAX_PLATFORMS="cpu")
    env.pop("MXNET_TELEMETRY", None)
    r = subprocess.run(
        [sys.executable, "-c",
         "from mxnet_tpu import telemetry, perfdebug; "
         "assert telemetry.enabled(); "
         "assert perfdebug.flight_enabled()"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert r.returncode == 0, r.stderr
