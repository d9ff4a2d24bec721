"""Training sentinel: hang watchdog, cross-replica integrity audits,
statistical anomaly rollback, supervised restarts (docs/resilience.md
"Watchdog, integrity audits & supervised restarts").

Pins the ISSUE-15 acceptance surface: ``fit.wedge`` at batch k → the
watchdog raises typed ``TrainingWedged`` within the deadline with a
flight-recorder + stack dump on disk → ``tools/supervise.py`` restarts
→ resume is bit-identical to an uninterrupted run (kill -9 recovers
the same way; budget exhaustion is a typed failure, not a crash loop);
``audit.bitflip`` on an 8-device mesh is caught by the next integrity
audit with ≤2%-of-step-time steady-state overhead; ``anomaly_policy``
handles a seeded loss spike via rollback-and-skip under the
consecutive-rollback budget.  ``ci/run_chaos.sh`` runs the slow
subprocess matrices with rotating ``MXNET_CHAOS_SEED``.
"""

import glob
import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import faults, sentinel, telemetry
from mxnet_tpu import io as mxio
from mxnet_tpu.base import MXNetError
from mxnet_tpu.retry import RetryPolicy, retry_call

CHAOS_SEED = int(os.environ.get("MXNET_CHAOS_SEED", "0"))

N, DIM, CLASSES, BATCH, EPOCHS = 64, 8, 3, 16, 2
BATCHES_PER_EPOCH = N // BATCH

_ENV = ("MXNET_WATCHDOG", "MXNET_WATCHDOG_ACTION",
        "MXNET_STEP_DEADLINE_FACTOR", "MXNET_STEP_DEADLINE_MS",
        "MXNET_HEARTBEAT_FILE", "MXNET_WEDGE_FAULT_S",
        "MXNET_AUDIT_EVERY_N_BATCHES", "MXNET_AUDIT_POLICY",
        "MXNET_ANOMALY_POLICY", "MXNET_ANOMALY_WINDOW",
        "MXNET_ANOMALY_ZSCORE", "MXNET_ROLLBACK_BUDGET",
        "MXNET_RESTART_BUDGET", "MXNET_RETRY_TOTAL_DEADLINE",
        "MXNET_FLIGHT_RECORDER_DIR", "MXNET_FAULT_SPEC",
        "MXNET_CKPT_EVERY_N_BATCHES", "MXNET_CKPT_ASYNC")

eight = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 virtual devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


@pytest.fixture(autouse=True)
def _clean():
    faults.disarm()
    telemetry.reset()
    telemetry.enable()
    # leave the global RNG streams exactly as found: these tests seed
    # np randomness for reproducibility, and downstream suite files
    # (convergence tests) are sensitive to the stream position they
    # inherit (same guard as tests/test_mesh_kvstore.py)
    np_state = np.random.get_state()
    from mxnet_tpu import random as _mx_random

    mx_state = _mx_random.get_state()
    yield
    np.random.set_state(np_state)
    _mx_random.set_state(mx_state)
    faults.disarm()
    telemetry.disable()
    telemetry.reset()
    for var in _ENV:
        os.environ.pop(var, None)
    assert not [t for t in threading.enumerate()
                if t.name == "sentinel-watchdog" and t.is_alive()], \
        "watchdog thread leaked past its fit"


def _toy_module(dim=DIM, classes=CLASSES, hidden=16):
    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, num_hidden=hidden, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(h, num_hidden=classes, name="fc2"),
        name="softmax")
    return mx.mod.Module(net, context=mx.cpu())


def _toy_xy(seed=7, n=N, dim=DIM, classes=CLASSES):
    rs = np.random.RandomState(seed + CHAOS_SEED)
    x = rs.rand(n, dim).astype(np.float32)
    y = rs.randint(0, classes, n).astype(np.float32)
    return x, y


def _fit(mod, x, y, num_epoch=EPOCHS, **kwargs):
    it = mxio.NDArrayIter(x, y, batch_size=BATCH, shuffle=False)
    mod.fit(it, num_epoch=num_epoch, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            **kwargs)
    return mod


# -- watchdog ----------------------------------------------------------------

def test_watchdog_deadline_calibrates_from_median_step():
    wd = sentinel.Watchdog(action="warn", factor=10.0, floor_ms=100.0)
    # startup grace until the first COMPLETED step: batch 0's fast
    # data-phase exit must not end it — the cold compile runs in the
    # forward_backward phase that follows
    assert wd.deadline_s() == pytest.approx(1.0)
    wd._on_phase("fit", "data", 0.0)              # batch 0 opens
    wd._on_phase("fit", "forward_backward", 0.0)  # compile done
    assert wd.deadline_s() == pytest.approx(1.0)  # grace still holds
    wd._on_phase("fit", "data", 0.0)              # step 0 completed
    assert wd.deadline_s() == pytest.approx(0.1)  # floor until 5 steps
    with wd._lock:
        wd._steps = [0.04, 0.05, 0.05, 0.06, 2.0]
    # median 0.05 x factor 10 = 0.5s — the 2s outlier does not set the
    # deadline, and the floor no longer does either
    assert wd.deadline_s() == pytest.approx(0.5)
    # a model whose median step EXCEEDS the floor/factor ratio raises
    # the deadline instead of false-tripping
    with wd._lock:
        wd._steps = [30.0] * 5
    assert wd.deadline_s() == pytest.approx(300.0)


def test_watchdog_phase_feed_closes_steps():
    wd = sentinel.Watchdog(action="warn", floor_ms=100.0)
    wd._on_phase("fit", "data", 0.0)      # opens batch 0
    wd._on_phase("fit", "forward_backward", 0.0)
    wd._on_phase("fit", "data", 0.0)      # closes step 1
    with wd._lock:
        assert len(wd._steps) == 1
    wd._on_phase("serving", "data", 0.0)  # liveness, not calibration
    with wd._lock:
        assert len(wd._steps) == 1
    # phase-free work ticks liveness through note_progress
    wd.start()
    try:
        with wd._lock:
            wd._last_progress = 0.0
        sentinel.note_progress()
        with wd._lock:
            assert wd._last_progress > 0.0
    finally:
        wd.stop()


def test_watchdog_heartbeat_file(tmp_path):
    hb = str(tmp_path / "hb.json")
    wd = sentinel.Watchdog(action="warn", floor_ms=100.0,
                           heartbeat_path=hb)
    wd.start()
    try:
        deadline = time.monotonic() + 5
        while not os.path.exists(hb) and time.monotonic() < deadline:
            time.sleep(0.02)
        assert os.path.exists(hb), "heartbeat never written"
        beat = json.load(open(hb))
        assert beat["pid"] == os.getpid()
        assert "progress_age_s" in beat
    finally:
        wd.stop()


def test_wedge_fault_trips_watchdog_typed_with_dump(tmp_path):
    """Acceptance: fit.wedge at batch k → TrainingWedged within the
    deadline, flight-recorder dump (with all-thread stacks) on disk."""
    os.environ.update({
        "MXNET_WATCHDOG": "1", "MXNET_STEP_DEADLINE_MS": "400",
        "MXNET_WEDGE_FAULT_S": "20",
        "MXNET_FLIGHT_RECORDER_DIR": str(tmp_path)})
    # wedge AFTER 5 completed steps: the warm-up deadline deliberately
    # carries the compile-heavy first steps at the full factor, so an
    # early wedge would (correctly) wait out that allowance
    faults.arm("fit.wedge", at=7)
    x, y = _toy_xy()
    t0 = time.monotonic()
    with pytest.raises(sentinel.TrainingWedged):
        _fit(_toy_module(), x, y)
    # raised by the watchdog (deadline 0.4s + injection slack), far
    # before the 20s the wedge itself would hold the step
    assert time.monotonic() - t0 < 10
    assert telemetry.counter_total("reliability.hangs") >= 1
    dumps = glob.glob(str(tmp_path / "flightrec-*-hang.json"))
    assert dumps, "no hang flight-recorder dump written"
    payload = json.load(open(dumps[0]))
    stacks = payload["detail"]["stacks"]
    assert any("wedge_sleep" in "".join(frames)
               for frames in stacks.values()), \
        "stack dump does not show the wedged thread"


def test_watchdog_warn_only_survives_the_wedge():
    os.environ.update({
        "MXNET_WATCHDOG": "1", "MXNET_WATCHDOG_ACTION": "warn",
        "MXNET_STEP_DEADLINE_MS": "300", "MXNET_WEDGE_FAULT_S": "1.0"})
    faults.arm("fit.wedge", at=7)  # past the 5-step calibration warm-up
    x, y = _toy_xy()
    mod = _fit(_toy_module(), x, y, num_epoch=2)
    assert telemetry.counter_total("reliability.hangs") >= 1
    arg, _ = mod.get_params()
    assert all(np.isfinite(v.asnumpy()).all() for v in arg.values())


def test_watchdog_no_extra_host_syncs_when_armed():
    """Watchdog-enabled fit must add NO device syncs to the hot loop:
    the sync-phase count (guard-flag/metric reads) is identical with
    and without the watchdog — its only hot-loop footprint is a
    timestamp store inside the phase hook."""
    x, y = _toy_xy()

    def sync_count():
        totals = telemetry.phase_totals("fit")
        return totals.get("sync", (0, 0))[1]

    _fit(_toy_module(), x, y, num_epoch=1)
    baseline = sync_count()
    telemetry.reset()
    os.environ.update({"MXNET_WATCHDOG": "1",
                       "MXNET_STEP_DEADLINE_MS": "60000"})
    _fit(_toy_module(), x, y, num_epoch=1)
    assert sync_count() == baseline


def test_watchdog_action_validated():
    with pytest.raises(MXNetError, match="raise/warn/exit"):
        sentinel.Watchdog(action="explode")


# -- SIGQUIT dump-on-demand --------------------------------------------------

@pytest.mark.skipif(not hasattr(signal, "SIGQUIT"),
                    reason="no SIGQUIT on this platform")
def test_sigquit_dumps_without_killing_the_run(tmp_path):
    os.environ["MXNET_FLIGHT_RECORDER_DIR"] = str(tmp_path)
    x, y = _toy_xy()
    fired = []

    def cb(p):
        if p.epoch == 0 and p.nbatch == 1 and not fired:
            fired.append(True)
            os.kill(os.getpid(), signal.SIGQUIT)

    mod = _fit(_toy_module(), x, y, batch_end_callback=cb)
    # the handler spawns the dump on a thread (lock-safety): wait for it
    deadline = time.monotonic() + 10
    dumps = []
    while not dumps and time.monotonic() < deadline:
        dumps = glob.glob(str(tmp_path / "flightrec-*-sigquit.json"))
        time.sleep(0.05)
    assert dumps, "SIGQUIT produced no dump"
    payload = json.load(open(dumps[0]))
    assert payload["detail"]["stacks"], "dump carries no thread stacks"
    # the run was NOT killed: it trained to the end
    arg, _ = mod.get_params()
    assert all(np.isfinite(v.asnumpy()).all() for v in arg.values())
    # and the previous handler was restored (signal-restore contract)
    assert signal.getsignal(signal.SIGQUIT) in (
        signal.SIG_DFL, signal.SIG_IGN, signal.default_int_handler)


# -- phase-hook registry (satellite: both consumers observe phases) ----------

def test_phase_hook_list_feeds_all_consumers():
    seen_a, seen_b = [], []
    ha = telemetry.add_phase_hook(
        lambda fam, ph, s: seen_a.append((fam, ph)))
    hb = telemetry.add_phase_hook(
        lambda fam, ph, s: seen_b.append((fam, ph)))
    try:
        with telemetry.phase("probe"):
            pass
        assert ("fit", "probe") in seen_a
        assert ("fit", "probe") in seen_b
    finally:
        telemetry.remove_phase_hook(ha)
        telemetry.remove_phase_hook(hb)


def test_watchdog_and_flight_recorder_share_the_phase_feed():
    """Regression for the single-slot eviction bug: with the flight
    recorder armed AND a watchdog started, one timed phase lands in
    BOTH the recorder ring and the watchdog's progress clock."""
    from mxnet_tpu import perfdebug

    perfdebug.enable_flight_recorder()
    wd = sentinel.Watchdog(action="warn", floor_ms=60000.0)
    wd.start()
    try:
        with wd._lock:
            wd._last_progress = 0.0  # ancient: the phase must refresh it
        with telemetry.phase("shared_probe"):
            pass
        with wd._lock:
            assert wd._last_progress > 0.0, "watchdog hook evicted"
        ring = [r for r in perfdebug._flight
                if r.get("kind") == "phase"
                and r.get("phase") == "shared_probe"]
        assert ring, "flight-recorder hook evicted"
    finally:
        wd.stop()
        # back to env-derived enablement (a forced False would mask the
        # MXNET_FLIGHT_RECORDER_DIR arming in later tests)
        perfdebug._flight_flag = None


# -- retry total deadline (satellite) ----------------------------------------

def test_retry_policy_deadline_s_alias():
    assert RetryPolicy(deadline_s=7.5).deadline == 7.5


def test_retry_total_deadline_caps_every_policy():
    os.environ["MXNET_RETRY_TOTAL_DEADLINE"] = "0.25"
    assert RetryPolicy(deadline=120).deadline == 0.25
    assert RetryPolicy().deadline == 0.25  # even the "forever" policy
    assert RetryPolicy(deadline=0.1).deadline == 0.1  # tighter wins


def test_retry_call_cumulative_deadline_bounds_the_stall():
    os.environ["MXNET_RETRY_TOTAL_DEADLINE"] = "0.3"
    calls = [0]

    def flaky():
        calls[0] += 1
        raise OSError("transient forever")

    t0 = time.monotonic()
    with pytest.raises(OSError):
        retry_call(flaky, policy=RetryPolicy(deadline=60,
                                             base_delay=0.02))
    assert time.monotonic() - t0 < 2.0
    assert calls[0] >= 2  # it did retry, then the cap ended it


# -- anomaly policy ----------------------------------------------------------

def _spiked_xy(spike_batches, scale=1e4, n=N * 3):
    """Toy data with whole input batches scaled sky-high: a finite
    loss/grad spike the NaN guard cannot see."""
    x, y = _toy_xy(n=n)
    for b in spike_batches:
        x[b * BATCH:(b + 1) * BATCH] *= scale
    return x, y


def test_anomaly_policy_validated():
    x, y = _toy_xy()
    with pytest.raises(MXNetError, match="anomaly_policy"):
        _fit(_toy_module(), x, y, anomaly_policy="explode")
    with pytest.raises(MXNetError, match="checkpoint_prefix"):
        _fit(_toy_module(), x, y, anomaly_policy="rollback")


def test_anomaly_raise_on_seeded_spike():
    # batch 9 of a 12-batch epoch: past the 8-observation warm-up
    x, y = _spiked_xy([9])
    with pytest.raises(MXNetError, match="anomaly"):
        _fit(_toy_module(), x, y, num_epoch=1, anomaly_policy="raise")
    assert telemetry.counter_total("reliability.anomalies") == 1


def test_anomaly_skip_matches_nan_skip_trajectory():
    """THE generalization pin: a finite gradient spike under
    anomaly_policy='skip_batch' ends bit-identical to the SAME batch
    being NaN-poisoned under nan_policy='skip_batch' — both withhold
    exactly that update, so 'a loss spike is handled like a NaN is
    today'."""
    spike_at = 9
    np.random.seed(11 + CHAOS_SEED)
    mod_a = _toy_module()
    x, y = _spiked_xy([spike_at])
    seen = []
    _fit(mod_a, x, y, num_epoch=1, anomaly_policy="skip_batch",
         batch_end_callback=lambda p: seen.append(
             (p.epoch, p.nbatch, p.anomaly_detected, p.anomaly_action)))
    assert (0, spike_at, True, "skip_batch") in seen
    np.random.seed(11 + CHAOS_SEED)
    mod_b = _toy_module()
    xb, yb = _toy_xy(n=N * 3)
    faults.arm("fit.batch", at=spike_at + 1)  # 1-based hit index
    _fit(mod_b, xb, yb, num_epoch=1, nan_policy="skip_batch")
    faults.disarm()
    arg_a, _ = mod_a.get_params()
    arg_b, _ = mod_b.get_params()
    for k in arg_a:
        np.testing.assert_array_equal(arg_a[k].asnumpy(),
                                      arg_b[k].asnumpy(), err_msg=k)


def _fake_norm_spikes(mod, spike_calls, value=1e9):
    """Spike the anomaly STATISTIC (not the data) on chosen global
    batches — 1-based call indices of ``_batch_grad_norm`` — so the
    trip machinery is exercised without destabilizing the underlying
    training trajectory."""
    calls = [0]
    orig = mod._batch_grad_norm

    def fake():
        calls[0] += 1
        real = orig()
        return value if calls[0] in spike_calls else real

    mod._batch_grad_norm = fake
    return calls


def test_anomaly_rollback_and_skip(tmp_path):
    # spike at epoch 2 batch 1 (global batch 9: past warm-up, and the
    # epoch-2 checkpoint exists to roll back to)
    x, y = _toy_xy()
    mod = _toy_module()
    _fake_norm_spikes(mod, {10})
    seen = []
    _fit(mod, x, y, num_epoch=3, anomaly_policy="rollback",
         checkpoint_prefix=str(tmp_path / "rb"),
         batch_end_callback=lambda p: seen.append(
             (p.epoch, p.nbatch, p.anomaly_detected, p.anomaly_action)))
    assert (2, 1, True, "rollback") in seen
    assert telemetry.counter_total("resilience.rollbacks") == 1
    assert telemetry.counter_total("reliability.anomalies") == 1
    arg, _ = mod.get_params()
    assert all(np.isfinite(v.asnumpy()).all() for v in arg.values())


def test_anomaly_consecutive_budget_exhausts_typed():
    # spikes on 4 consecutive post-warm-up batches: trips 1..3 are
    # skipped under the default budget of 3, the 4th is the typed end
    x, y = _toy_xy()
    mod = _toy_module()
    _fake_norm_spikes(mod, {9, 10, 11, 12})
    with pytest.raises(sentinel.AnomalyBudgetExhausted):
        _fit(mod, x, y, num_epoch=4, anomaly_policy="skip_batch")
    assert telemetry.counter_total("reliability.anomalies") == 4


def test_anomaly_budget_resets_on_clean_batch():
    # spikes with a clean batch between: never more than 1 consecutive,
    # so even a budget of 1 survives all three
    x, y = _toy_xy()
    mod = _toy_module()
    _fake_norm_spikes(mod, {9, 11, 13})
    os.environ["MXNET_ROLLBACK_BUDGET"] = "1"
    _fit(mod, x, y, num_epoch=4, anomaly_policy="skip_batch")
    assert telemetry.counter_total("reliability.anomalies") == 3
    arg, _ = mod.get_params()
    assert all(np.isfinite(v.asnumpy()).all() for v in arg.values())


def test_anomaly_detector_unit():
    det = sentinel.AnomalyDetector(window=16, zscore=6.0)
    for i in range(12):
        assert not det.observe(1.0 + 0.01 * (i % 3))
    assert det.observe(100.0)          # spike flagged...
    assert not det.observe(1.01)       # ...and not folded into baseline
    assert det.observe(float("nan"))   # non-finite is always anomalous
    assert det.observe(float("inf"))
    with pytest.raises(MXNetError):
        sentinel.AnomalyDetector(window=2)


def test_anomaly_detector_robust_to_warmup_outlier():
    """A spike that slipped into the window during warm-up must not
    hide later spikes (median/MAD baseline, not mean/std)."""
    det = sentinel.AnomalyDetector(window=32, zscore=6.0)
    det.observe(300000.0)  # warm-up outlier, absorbed
    for i in range(10):
        assert not det.observe(1.0 + 0.01 * (i % 3))
    assert det.observe(330000.0), \
        "warm-up outlier poisoned the baseline"


# -- cross-replica integrity audits ------------------------------------------

def _mesh_fit(mod, x, y, num_epoch=EPOCHS, **kwargs):
    it = mxio.NDArrayIter(x, y, batch_size=BATCH, shuffle=False)
    mod.fit(it, num_epoch=num_epoch, optimizer="sgd", kvstore="mesh",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            **kwargs)
    return mod


@eight
def test_audit_clean_mesh_fit_counts_audits():
    os.environ["MXNET_AUDIT_EVERY_N_BATCHES"] = "2"
    x, y = _toy_xy(dim=16, classes=8)
    _mesh_fit(_toy_module(dim=16, classes=8, hidden=32), x, y)
    total = EPOCHS * BATCHES_PER_EPOCH
    assert telemetry.counter_total("reliability.audits") == total // 2
    assert telemetry.counter_total("reliability.divergences") == 0


@eight
def test_audit_bitflip_caught_by_next_audit(tmp_path):
    """Acceptance: audit.bitflip on an 8-device mesh → the NEXT audit
    catches it as typed ReplicaDivergence, with the divergence event
    naming the corrupted array."""
    os.environ.update({"MXNET_AUDIT_EVERY_N_BATCHES": "2",
                       "MXNET_FLIGHT_RECORDER_DIR": str(tmp_path)})
    faults.arm("audit.bitflip", at=1)
    x, y = _toy_xy(dim=16, classes=8)
    with pytest.raises(sentinel.ReplicaDivergence, match="diverged"):
        _mesh_fit(_toy_module(dim=16, classes=8, hidden=32), x, y)
    assert telemetry.counter_total("reliability.divergences") == 1
    events = [e for e in telemetry.events_recent()
              if e["event"] == "reliability.divergence"]
    assert events and events[0]["first"].startswith("fc")
    assert glob.glob(str(tmp_path / "flightrec-*-divergence.json"))


@eight
def test_audit_bitflip_rollback_policy_recovers(tmp_path):
    os.environ.update({"MXNET_AUDIT_EVERY_N_BATCHES": "2",
                       "MXNET_AUDIT_POLICY": "rollback"})
    # trip on the second audit so the epoch-1 checkpoint exists
    faults.arm("audit.bitflip", at=BATCHES_PER_EPOCH // 2 + 1)
    x, y = _toy_xy(dim=16, classes=8)
    mod = _mesh_fit(_toy_module(dim=16, classes=8, hidden=32), x, y,
                    checkpoint_prefix=str(tmp_path / "rb"))
    assert telemetry.counter_total("reliability.divergences") == 1
    assert telemetry.counter_total("resilience.rollbacks") == 1
    arg, _ = mod.get_params()
    assert all(np.isfinite(v.asnumpy()).all() for v in arg.values())


@eight
def test_audit_rollback_policy_requires_prefix():
    os.environ.update({"MXNET_AUDIT_EVERY_N_BATCHES": "2",
                       "MXNET_AUDIT_POLICY": "rollback"})
    x, y = _toy_xy(dim=16, classes=8)
    with pytest.raises(MXNetError, match="checkpoint_prefix"):
        _mesh_fit(_toy_module(dim=16, classes=8, hidden=32), x, y)


def test_audit_noop_off_the_mesh_plane():
    """audit cadence on a plain local fit: no mesh replicas to compare
    — skipped (debug-logged), zero audits, fit unharmed."""
    os.environ["MXNET_AUDIT_EVERY_N_BATCHES"] = "1"
    x, y = _toy_xy()
    _fit(_toy_module(), x, y, num_epoch=1)
    assert telemetry.counter_total("reliability.audits") == 0


@eight
def test_audit_overhead_within_two_percent_of_step_time():
    """Acceptance: steady-state audit cost ≤ 2% of step time at the
    documented cadence (100).  Pinned from telemetry itself: the audit
    phase's fastest observation (compile excluded) against the mean
    per-batch phase cost, scaled by the cadence."""
    cadence = 100
    os.environ["MXNET_AUDIT_EVERY_N_BATCHES"] = "10"  # more samples
    n = 32 * 40
    x, y = _toy_xy(n=n, dim=64, classes=8)
    it = mxio.NDArrayIter(x, y, batch_size=32, shuffle=False)
    mod = _toy_module(dim=64, classes=8, hidden=256)
    mod.fit(it, num_epoch=2, optimizer="sgd", kvstore="mesh",
            optimizer_params={"learning_rate": 0.1})
    snap = telemetry.snapshot()["histograms"]["fit.phase_seconds"]
    audit = next(v for k, v in snap.items() if "audit" in k)
    assert audit["count"] >= 4
    step_mean = sum(v["mean"] for k, v in snap.items()
                    if "audit" not in k)
    assert audit["min"] <= 0.02 * cadence * step_mean, \
        "steady-state audit %.5fs vs budget %.5fs (step %.5fs)" % (
            audit["min"], 0.02 * cadence * step_mean, step_mean)


# -- supervisor --------------------------------------------------------------

def _write_script(tmp_path, name, body):
    p = tmp_path / name
    p.write_text(textwrap.dedent(body))
    return str(p)


def test_supervisor_restarts_until_success(tmp_path):
    """Cheap child (no framework import): dies twice, then succeeds —
    the supervisor restarts through it and reports the restart count."""
    marker = str(tmp_path / "attempts")
    script = _write_script(tmp_path, "flaky.py", """
        import os, sys
        path = %r
        n = int(open(path).read()) if os.path.exists(path) else 0
        open(path, "w").write(str(n + 1))
        sys.exit(0 if n >= 2 else 1)
        """ % marker)
    sup = sentinel.Supervisor([sys.executable, script], budget=5,
                              backoff_base=0.05, poll_s=0.05)
    assert sup.run() == 0
    assert sup.restarts == 2


def test_supervisor_budget_exhaustion_is_typed(tmp_path):
    script = _write_script(tmp_path, "dies.py",
                           "import sys; sys.exit(3)\n")
    sup = sentinel.Supervisor([sys.executable, script], budget=2,
                              backoff_base=0.02, poll_s=0.05)
    with pytest.raises(sentinel.RestartBudgetExhausted) as ei:
        sup.run()
    assert ei.value.restarts == 2
    assert ei.value.last_exit == 3


def test_supervisor_heartbeat_stale_kills_wedged_child(tmp_path):
    """A live-but-silent child (its heartbeat stops) is killed hard and
    restarted — the process-level answer to a hang the in-process
    watchdog could not unwind."""
    hb = str(tmp_path / "hb.json")
    marker = str(tmp_path / "ran")
    script = _write_script(tmp_path, "wedges.py", """
        import json, os, sys, time
        hb, marker = %r, %r
        if os.path.exists(marker):
            sys.exit(0)          # restarted run succeeds
        open(marker, "w").write("1")
        json.dump({"ts": time.time()}, open(hb, "w"))
        time.sleep(600)          # wedged: heartbeat never refreshes
        """ % (hb, marker))
    sup = sentinel.Supervisor([sys.executable, script], budget=3,
                              backoff_base=0.05, poll_s=0.1,
                              heartbeat_path=hb, heartbeat_timeout=1.0)
    t0 = time.monotonic()
    assert sup.run() == 0
    assert sup.restarts == 1
    assert time.monotonic() - t0 < 60


def test_supervisor_never_heartbeat_startup_grace_is_bounded(tmp_path):
    """A child wedged BEFORE it ever writes a heartbeat (hung import,
    stuck rendezvous) must still be killed — after 2x the timeout as
    startup allowance — not polled forever."""
    hb = str(tmp_path / "hb.json")
    marker = str(tmp_path / "ran")
    script = _write_script(tmp_path, "silent.py", """
        import os, sys, time
        marker = %r
        if os.path.exists(marker):
            sys.exit(0)
        open(marker, "w").write("1")
        time.sleep(600)   # wedged at startup: heartbeat never written
        """ % marker)
    sup = sentinel.Supervisor([sys.executable, script], budget=2,
                              backoff_base=0.05, poll_s=0.1,
                              heartbeat_path=hb, heartbeat_timeout=0.5)
    t0 = time.monotonic()
    assert sup.run() == 0
    assert sup.restarts == 1
    assert time.monotonic() - t0 < 60


def test_supervisor_budget_resets_after_healthy_uptime(tmp_path):
    """The budget bounds the CRASH LOOP, not the job's lifetime: a
    child that ran healthy past healthy_reset_s before dying resets
    the counter (two spaced deaths survive a budget of 1 that two
    rapid deaths would exhaust)."""
    marker = str(tmp_path / "attempts")
    script = _write_script(tmp_path, "spaced.py", """
        import os, sys, time
        path = %r
        n = int(open(path).read()) if os.path.exists(path) else 0
        open(path, "w").write(str(n + 1))
        if n >= 2:
            sys.exit(0)
        time.sleep(0.7)   # "healthy" uptime before the death
        sys.exit(1)
        """ % marker)
    sup = sentinel.Supervisor([sys.executable, script], budget=1,
                              backoff_base=0.05, poll_s=0.05,
                              healthy_reset_s=0.5)
    assert sup.run() == 0
    assert sup.restarts == 1  # counter was reset between the deaths


def test_supervise_cli_exit_codes(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    try:
        import supervise
    finally:
        sys.path.pop(0)
    ok = _write_script(tmp_path, "ok.py", "raise SystemExit(0)\n")
    assert supervise.main(["--budget", "1", "--", sys.executable,
                           ok]) == 0
    bad = _write_script(tmp_path, "bad.py", "raise SystemExit(9)\n")
    assert supervise.main(["--budget", "1", "--backoff-base", "0.02",
                           "--", sys.executable, bad]) == 75
    with pytest.raises(SystemExit):
        supervise.main(["--budget", "1"])  # no command


# -- chaos acceptance (subprocess training runs; ci/run_chaos.sh matrix) -----

_CHILD = """
import os, sys
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import faults
from mxnet_tpu import io as mxio

seed = int(os.environ.get("MXNET_CHAOS_SEED", "0"))
prefix, out, marker, mode = sys.argv[1:5]
kill_at = int(sys.argv[5])
N, DIM, CLASSES, BATCH = 64, 8, 3, 16
rs = np.random.RandomState(7 + seed)
x = rs.rand(N, DIM).astype(np.float32)
y = rs.randint(0, CLASSES, N).astype(np.float32)
data = mx.sym.Variable("data")
h = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
h = mx.sym.Activation(h, act_type="relu")
net = mx.sym.SoftmaxOutput(
    mx.sym.FullyConnected(h, num_hidden=CLASSES, name="fc2"),
    name="softmax")
mod = mx.mod.Module(net, context=mx.cpu())
first = not os.path.exists(marker)
if first:
    open(marker, "w").write("1")
    if mode == "wedge":
        faults.arm("fit.wedge", at=kill_at)

cb = None
if first and mode == "kill9":
    import signal as _s
    count = [0]

    def cb(p):
        count[0] += 1
        if count[0] == kill_at:  # global batch count (spans epochs)
            os.kill(os.getpid(), _s.SIGKILL)

np.random.seed(11 + seed)
it = mxio.NDArrayIter(x, y, batch_size=BATCH, shuffle=False)
mod.fit(it, num_epoch=2, optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        checkpoint_prefix=prefix, checkpoint_every_n_batches=1,
        resume="auto", batch_end_callback=cb)
arg, _aux = mod.get_params()
np.savez(out, **{k: v.asnumpy() for k, v in arg.items()})
"""


def _chaos_env(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "MXNET_WATCHDOG": "1",
                "MXNET_STEP_DEADLINE_MS": "500",
                "MXNET_WEDGE_FAULT_S": "30", "MXNET_CKPT_ASYNC": "0",
                "MXNET_FLIGHT_RECORDER_DIR": str(tmp_path / "fr"),
                # the child script lives in tmp: the framework import
                # must resolve from the repo regardless
                "PYTHONPATH": repo + os.pathsep
                + env.get("PYTHONPATH", "")})
    return env


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["wedge", "kill9"])
def test_supervised_restart_resumes_bit_identical(tmp_path, mode):
    """THE chaos acceptance: wedge (watchdog raises out of the child)
    or kill -9 at batch k → tools/supervise-style restart → resume →
    final params BIT-IDENTICAL to a never-interrupted run."""
    script = _write_script(tmp_path, "child.py", _CHILD)
    env = _chaos_env(tmp_path)
    # past the watchdog's 5-step calibration warm-up (the wedge variant
    # would otherwise sit under the compile-inflated warm-up deadline);
    # global batch 6..8 of the child's 8-batch run
    kill_at = 6 + (CHAOS_SEED % 3)

    def run(tag, premark):
        prefix = str(tmp_path / (tag + "-ck"))
        out = str(tmp_path / (tag + ".npz"))
        marker = str(tmp_path / (tag + ".marker"))
        if premark:
            open(marker, "w").write("1")
        sup = sentinel.Supervisor(
            [sys.executable, script, prefix, out, marker, mode,
             str(kill_at)],
            budget=3, backoff_base=0.05, poll_s=0.1)
        saved = dict(os.environ)
        os.environ.update(env)
        try:
            assert sup.run() == 0
        finally:
            os.environ.clear()
            os.environ.update(saved)
        return np.load(out), sup.restarts

    ref, ref_restarts = run("ref", premark=True)
    assert ref_restarts == 0
    got, restarts = run(mode, premark=False)
    assert restarts == 1, "the %s child should die exactly once" % mode
    for k in ref.files:
        np.testing.assert_array_equal(got[k], ref[k], err_msg=k)
    if mode == "wedge":
        dumps = glob.glob(str(tmp_path / "fr" / "flightrec-*-hang.json"))
        assert dumps, "child left no hang dump"


@pytest.mark.slow
def test_supervised_crash_loop_exhausts_budget(tmp_path):
    """Budget exhaustion on a training child that dies EVERY run (its
    marker path is unwritable, so every launch crashes at startup):
    typed failure out of the supervisor, not an infinite restart
    loop."""
    script = _write_script(tmp_path, "child.py", _CHILD)
    env = _chaos_env(tmp_path)
    prefix = str(tmp_path / "loop-ck")
    out = str(tmp_path / "loop.npz")
    missing_marker = str(tmp_path / "never-created" / "marker")
    sup = sentinel.Supervisor(
        [sys.executable, script, prefix, out, missing_marker, "wedge",
         "2"],
        budget=1, backoff_base=0.05, poll_s=0.1)
    saved = dict(os.environ)
    os.environ.update(env)
    try:
        with pytest.raises(sentinel.RestartBudgetExhausted):
            sup.run()
    finally:
        os.environ.clear()
        os.environ.update(saved)
