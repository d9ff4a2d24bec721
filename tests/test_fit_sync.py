"""Sync-free ``Module.fit`` suite (docs/how_to/perf.md): device-resident
metrics (exact-value parity with the host path), the fused in-graph NaN
guard (all three policies, fused and two-phase, amortized cadence),
device-side prefetch (numerical identity), and the graftlint
``host-sync`` pass that keeps the hot path honest."""

import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faults, io, metric
from mxnet_tpu.base import MXNetError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    faults.disarm()
    yield
    faults.disarm()
    for var in ("MXNET_FAULT_SPEC", "MXNET_FUSE_TRAIN_STEP",
                "MXNET_DEVICE_METRIC", "MXNET_DEVICE_PREFETCH",
                "MXNET_NAN_CHECK_PERIOD"):
        os.environ.pop(var, None)


def _toy_dataset(n=64, d=8, classes=3, seed=7):
    rs = np.random.RandomState(seed)
    x = rs.rand(n, d).astype(np.float32)
    y = rs.randint(0, classes, n).astype(np.float32)
    return x, y


def _toy_iter(batch_size=16):
    x, y = _toy_dataset()
    return mx.io.NDArrayIter(x, y, batch_size=batch_size, shuffle=False)


def _toy_module():
    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(h, num_hidden=3, name="fc2"), name="softmax")
    return mx.mod.Module(net, context=mx.cpu())


def _fit(num_epoch=1, metric_arg="acc", seed=5, callbacks=None, **kwargs):
    mx.random.seed(seed)
    np.random.seed(seed)
    mod = _toy_module()
    mod.fit(_toy_iter(), num_epoch=num_epoch, eval_metric=metric_arg,
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.init.Xavier(),
            batch_end_callback=callbacks, **kwargs)
    return mod


# -- device-resident metrics ------------------------------------------------

def test_fit_auto_selects_device_metric():
    seen = []
    _fit(callbacks=lambda p: seen.append(p.eval_metric))
    assert seen and all(isinstance(m, metric.DeviceMetric) for m in seen)


def test_fit_env_disables_device_metric(monkeypatch):
    monkeypatch.setenv("MXNET_DEVICE_METRIC", "0")
    seen = []
    _fit(callbacks=lambda p: seen.append(p.eval_metric))
    assert seen and not any(isinstance(m, metric.DeviceMetric)
                            for m in seen)


def test_subclass_overriding_update_falls_back_to_host():
    """A user subclass of a builtin metric that overrides update() with
    custom semantics must NOT be auto-wrapped: the device path would
    silently compute the parent's statistics."""
    class MaskedAccuracy(metric.Accuracy):
        def update(self, labels, preds):  # e.g. ignore padding labels
            pass

    assert not metric.device_capable(MaskedAccuracy())
    assert not isinstance(metric.as_device(MaskedAccuracy()),
                          metric.DeviceMetric)
    # plain builtins and alias subclasses that inherit BOTH stay capable
    assert metric.device_capable(metric.Accuracy())
    assert metric.device_capable(metric.Torch())


def test_score_during_guarded_fit_is_not_gated(monkeypatch):
    """score() while the NaN guard is armed must not inherit the last
    TRAINING batch's flag as a metric gate — eval forwards clear it."""
    monkeypatch.setenv("MXNET_FUSE_TRAIN_STEP", "1")
    faults.arm("fit.batch", at=4)  # flag the LAST batch of the epoch
    mod = _fit(nan_policy="skip_batch")
    faults.disarm()
    assert mod._exec._nan_guard  # still armed after fit
    it = _toy_iter()
    m = mx.metric.Accuracy()
    mod.score(it, m)
    gated = m.get()[1]
    mod._install_nan_guard(None)
    it.reset()
    m2 = mx.metric.Accuracy()
    mod.score(it, m2)
    assert np.isfinite(gated)
    assert gated == m2.get()[1]


def test_custom_metric_falls_back_to_host():
    def feval(label, pred):
        return float((np.argmax(pred, axis=1) == label).mean())

    seen = []
    _fit(metric_arg=mx.metric.np(feval),
         callbacks=lambda p: seen.append(p.eval_metric))
    assert seen and not any(isinstance(m, metric.DeviceMetric)
                            for m in seen)
    assert np.isfinite(seen[-1].get()[1])


def _fit_metric_values(monkeypatch, device, metric_arg, num_epoch=2):
    monkeypatch.setenv("MXNET_DEVICE_METRIC", "1" if device else "0")
    finals = []
    _fit(num_epoch=num_epoch, metric_arg=metric_arg,
         callbacks=lambda p: finals.append(
             (p.nbatch, dict(p.eval_metric.get_name_value()))
             if p.nbatch == 3 else None))
    return [f for f in finals if f is not None]


def test_device_metric_fit_parity(monkeypatch):
    """LeNet/MNIST-scale fit: device-path metric values match the host
    path — accuracy exactly (integral sums in f32), cross-entropy to
    accumulation-order rounding (documented in docs/how_to/perf.md)."""
    make = lambda: ["accuracy", mx.metric.CrossEntropy()]  # noqa: E731
    host = _fit_metric_values(monkeypatch, False, make())
    dev = _fit_metric_values(monkeypatch, True, make())
    assert len(host) == len(dev) == 2  # one read per epoch
    for (hb, hv), (db, dv) in zip(host, dev):
        assert hb == db and set(hv) == set(dv)
        assert hv["accuracy"] == dv["accuracy"]
        np.testing.assert_allclose(dv["cross-entropy"],
                                   hv["cross-entropy"], rtol=1e-5)


def test_device_metric_bulk_fit_parity(monkeypatch):
    """MXNET_BULK_TRAIN_STEPS path: the device metric consumes run_bulk's
    stacked outputs without the host transfer — same values either way."""
    monkeypatch.setenv("MXNET_FUSE_TRAIN_STEP", "1")
    monkeypatch.setenv("MXNET_BULK_TRAIN_STEPS", "2")
    make = lambda: ["accuracy", mx.metric.CrossEntropy()]  # noqa: E731
    host = _fit_metric_values(monkeypatch, False, make(), num_epoch=1)
    dev = _fit_metric_values(monkeypatch, True, make(), num_epoch=1)
    assert host and dev
    assert host[0][1]["accuracy"] == dev[0][1]["accuracy"]
    np.testing.assert_allclose(dev[0][1]["cross-entropy"],
                               host[0][1]["cross-entropy"], rtol=1e-5)


def test_device_metric_score_parity(monkeypatch):
    mod = _fit()
    it = _toy_iter()
    vals = {}
    for device in (False, True):
        monkeypatch.setenv("MXNET_DEVICE_METRIC",
                           "1" if device else "0")
        m = mx.metric.CompositeEvalMetric(
            ["accuracy", mx.metric.CrossEntropy(), "mse"])
        it.reset()
        mod.score(it, m)
        # the caller's metric object is folded into at the final sync
        vals[device] = dict(m.get_name_value())
    assert vals[True]["accuracy"] == vals[False]["accuracy"]
    for name in ("cross-entropy", "mse"):
        np.testing.assert_allclose(vals[True][name], vals[False][name],
                                   rtol=1e-5)


def test_device_metric_keeps_evalmetric_attribute_surface():
    """Callbacks read the documented EvalMetric fields on whatever fit
    puts in BatchEndParam — the wrapper must expose them (synced)."""
    counts = []
    _fit(callbacks=lambda p: counts.append(p.eval_metric.num_inst))
    assert counts == [16, 32, 48, 64]
    m = metric.as_device(metric.Accuracy())
    assert m.num_inst == 0 and m.sum_metric == 0.0


def test_speedometer_reads_device_metric_only_at_cadence():
    """Rate reporting must not force a per-batch metric sync: with a
    DeviceMetric the only syncs are the Speedometer's frequent-cadence
    read and the epoch-end summary (4 batches, frequent=2 -> exactly 2)."""
    seen = []
    speedo = mx.callback.Speedometer(16, frequent=2)
    _fit(callbacks=[speedo, lambda p: seen.append(p.eval_metric)])
    m = seen[-1]
    assert isinstance(m, metric.DeviceMetric)
    assert m.sync_count == 2  # one mid-epoch log + one epoch-end read


# -- fused / amortized NaN guard -------------------------------------------

def test_nan_policy_raise_fused(monkeypatch):
    monkeypatch.setenv("MXNET_FUSE_TRAIN_STEP", "1")
    faults.arm("fit.batch", at=2)
    with pytest.raises(MXNetError, match="NaN/Inf"):
        _fit(nan_policy="raise")


def test_nan_policy_skip_batch_fused(monkeypatch):
    monkeypatch.setenv("MXNET_FUSE_TRAIN_STEP", "1")
    faults.arm("fit.batch", at=2)
    seen = []
    mod = _fit(nan_policy="skip_batch",
               callbacks=lambda p: seen.append(
                   (p.nbatch, p.nan_detected, p.nan_action)))
    assert [s for s in seen if s[1]] == [(1, True, "skip_batch")]
    arg, _ = mod.get_params()
    for k, v in arg.items():
        assert np.isfinite(v.asnumpy()).all(), k


def test_nan_policy_rollback_fused(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_FUSE_TRAIN_STEP", "1")
    # 4 batches/epoch; fire on the first batch of epoch 2 so the epoch-1
    # checkpoint exists to roll back to
    faults.arm("fit.batch", at=5)
    seen = []
    mod = _fit(num_epoch=2, nan_policy="rollback",
               checkpoint_prefix=str(tmp_path / "rb"),
               callbacks=lambda p: seen.append(
                   (p.epoch, p.nbatch, p.nan_detected, p.nan_action)))
    assert (1, 0, True, "rollback") in seen
    arg, _ = mod.get_params()
    for k, v in arg.items():
        assert np.isfinite(v.asnumpy()).all(), k


@pytest.mark.parametrize("fused", [False, True])
def test_nan_check_period_amortized_detection(monkeypatch, fused):
    """nan_check_period=3: the fault fires at batch 1, the flag read at
    batch 2 (the first check batch) reports it — detection latency, not
    loss."""
    monkeypatch.setenv("MXNET_FUSE_TRAIN_STEP", "1" if fused else "0")
    faults.arm("fit.batch", at=2)
    seen = []
    _fit(nan_policy="skip_batch", nan_check_period=3,
         callbacks=lambda p: seen.append((p.nbatch, p.nan_detected)))
    assert [s for s in seen if s[1]] == [(2, True)]


def test_nan_guard_in_graph_gate_keeps_params_finite(monkeypatch):
    """Natural divergence (absurd lr) in FUSED mode: the in-graph gate
    withholds every non-finite update, so parameters stay finite even
    though batch after batch flags — no fault injection, this exercises
    the genuinely fused reduction+gate."""
    monkeypatch.setenv("MXNET_FUSE_TRAIN_STEP", "1")
    seen = []
    metrics = []
    mx.random.seed(5)
    np.random.seed(5)
    mod = _toy_module()
    mod.fit(_toy_iter(), num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 1e30},
            initializer=mx.init.Xavier(), nan_policy="skip_batch",
            eval_metric=["accuracy", mx.metric.CrossEntropy()],
            batch_end_callback=lambda p: (seen.append(p.nan_detected),
                                          metrics.append(p.eval_metric)))
    assert any(seen)
    arg, _ = mod.get_params()
    for k, v in arg.items():
        assert np.isfinite(v.asnumpy()).all(), k
    # flagged batches' statistics were zeroed inside the metric jit, so
    # the epoch metric stays finite despite the NaN outputs
    for _name, val in metrics[-1].get_name_value():
        assert np.isfinite(val), metrics[-1].get_name_value()


def test_nan_guard_disarms_between_fits(monkeypatch):
    """A fit without nan_policy must DISARM a previous fit's guard and
    drop its accumulated flag — a stale flag used to make a later
    nan_policy='raise' fit abort on a perfectly clean batch."""
    monkeypatch.setenv("MXNET_FUSE_TRAIN_STEP", "1")
    mx.random.seed(5)
    np.random.seed(5)
    mod = _toy_module()
    it = _toy_iter()
    fit_kw = dict(optimizer="sgd",
                  optimizer_params={"learning_rate": 0.1},
                  initializer=mx.init.Xavier(), num_epoch=1)
    faults.arm("fit.batch", at=2)
    mod.fit(it, nan_policy="skip_batch", **fit_kw)
    faults.disarm()
    it.reset()
    mod.fit(it, **fit_kw)  # no policy: must disarm + clear
    assert mod._exec._nan_guard is False
    assert mod._exec._nan_acc is None
    it.reset()
    mod.fit(it, nan_policy="raise", **fit_kw)  # clean data: no raise


def test_nan_check_period_validation():
    with pytest.raises(MXNetError, match="nan_check_period"):
        _fit(nan_policy="skip_batch", nan_check_period=0)


# -- device-side prefetch ---------------------------------------------------

def _fit_params(prefetch, seed=3):
    mx.random.seed(seed)
    np.random.seed(seed)
    mod = _toy_module()
    mod.fit(_toy_iter(), num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.init.Xavier(), prefetch_to_device=prefetch)
    return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}


def test_prefetch_to_device_numerical_identity():
    plain = _fit_params(False)
    pre = _fit_params(True)
    assert set(plain) == set(pre)
    for k in plain:
        assert np.array_equal(plain[k], pre[k]), k


def test_prefetch_leaves_train_data_reset():
    """fit's postcondition: train_data comes back reset and UNTOUCHED by
    the (closed) producer thread — a final wrapper reset used to re-arm
    the producer, which could steal the first post-fit batch."""
    mx.random.seed(3)
    np.random.seed(3)
    it = _toy_iter()
    mod = _toy_module()
    mod.fit(it, num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.init.Xavier(), prefetch_to_device=True)
    assert len(list(it)) == 4  # the full epoch, starting at batch 0


def test_prefetch_env_default(monkeypatch):
    monkeypatch.setenv("MXNET_DEVICE_PREFETCH", "1")
    pre = _fit_params(None)  # fit reads the env default
    assert all(np.isfinite(v).all() for v in pre.values())


def test_device_prefetch_iter_places_batches():
    import jax

    dev = jax.devices("cpu")[0]
    x, y = _toy_dataset()
    inner = mx.io.NDArrayIter(x, y, batch_size=16, shuffle=False)
    with io.DevicePrefetchIter(inner, device=dev) as it:
        batches = list(it)
        assert len(batches) == 4
        for b in batches:
            for arr in list(b.data) + list(b.label):
                assert dev in arr._jx.devices()
        np.testing.assert_array_equal(batches[0].data[0].asnumpy(),
                                      x[:16])
    assert not any(t.is_alive() for t in it.prefetch_threads)


# -- host-sync lint (graftlint; the check_host_sync.py shim is gone) --------

def _run_host_sync(*args):
    return subprocess.run(
        [sys.executable, "-m", "ci.graftlint", "--pass", "host-sync",
         *[str(a) for a in args]],
        capture_output=True, text=True, cwd=ROOT, timeout=300)


def test_check_host_sync_hot_path_is_clean():
    res = _run_host_sync()
    assert res.returncode == 0, res.stdout + res.stderr


def test_check_host_sync_flags_and_tags(tmp_path):
    bad = tmp_path / "hot.py"
    bad.write_text(
        "import numpy as np\n"
        "def f(a):\n"
        "    v = a.asnumpy()\n"
        "    w = np.asarray(a)\n"
        "    ok = np.asarray([1.0])  # host-sync: ok — host literal\n"
        "    return v, w, ok\n")
    res = _run_host_sync(str(bad))
    assert res.returncode == 1
    assert "hot.py:3" in res.stdout and "hot.py:4" in res.stdout
    assert "hot.py:5" not in res.stdout
