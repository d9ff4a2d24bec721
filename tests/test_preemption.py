"""Preemption-tolerant training: kill/resume chaos harness.

TPU pods preempt; the recovery contract (docs/resilience.md "Preemption
& exact resume") is that a worker killed at an ARBITRARY batch resumes
to a state bit-identical to a never-killed run: async batch-granular
snapshots capture params + optimizer states + RNG + metric sums + the
iterator position, `fit` drains gracefully on SIGTERM/SIGINT (finish
the in-flight batch, flush accumulators, write a final snapshot, raise
`TrainingPreempted`), and `resume="auto"` restores all of it.

The kill half is the deterministic `fit.preempt` fault — a REAL SIGTERM
delivered to this process at batch k — so every scenario here replays
exactly.  `ci/run_chaos.sh` runs the matrix 5x with rotating seeds
(`MXNET_CHAOS_SEED`).
"""

import json
import os
import signal
import threading
import time
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faults, telemetry
from mxnet_tpu import io as mxio
from mxnet_tpu import recordio
from mxnet_tpu.base import MXNetError
from mxnet_tpu.checkpoint import (AsyncSnapshotWriter, TrainingPreempted,
                                  gc_snapshots, load_latest_state,
                                  snapshot_path)
from mxnet_tpu.model import checkpoint_manifest, load_latest_checkpoint

CHAOS_SEED = int(os.environ.get("MXNET_CHAOS_SEED", "0"))

#: toy problem geometry: 2 epochs x 4 batches (64 samples / batch 16)
N, DIM, CLASSES, BATCH, EPOCHS = 64, 8, 3, 16, 2
BATCHES_PER_EPOCH = N // BATCH

_CKPT_ENV = ("MXNET_CKPT_EVERY_N_BATCHES", "MXNET_CKPT_KEEP_LAST",
             "MXNET_CKPT_ASYNC", "MXNET_FAULT_SPEC")


@pytest.fixture(autouse=True)
def _clean():
    faults.disarm()
    telemetry.reset()
    telemetry.enable()
    yield
    faults.disarm()
    telemetry.disable()
    telemetry.reset()
    for var in _CKPT_ENV:
        os.environ.pop(var, None)


def _no_writer_threads():
    return not [t for t in threading.enumerate()
                if t.name == "ckpt-writer" and t.is_alive()]


def _toy_data(seed=7):
    rs = np.random.RandomState(seed + CHAOS_SEED)
    x = rs.rand(N, DIM).astype(np.float32)
    y = rs.randint(0, CLASSES, N).astype(np.float32)
    return x, y


def _toy_iter(seed=7):
    x, y = _toy_data(seed)
    return mxio.NDArrayIter(x, y, batch_size=BATCH, shuffle=False)


def _toy_module():
    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(h, num_hidden=CLASSES, name="fc2"),
        name="softmax")
    return mx.mod.Module(net, context=mx.cpu())


def _init_args():
    mod = _toy_module()
    it = _toy_iter()
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    np.random.seed(11 + CHAOS_SEED)
    mod.init_params(mx.init.Xavier())
    return mod.get_params()


def _cp(d):
    # deep-copy: the fused train step donates buffers, so arrays handed
    # to one fit must not be reused by the next
    return None if d is None else \
        {k: mx.nd.array(v.asnumpy()) for k, v in d.items()}


def _fit(prefix, arg_params=None, aux_params=None, metric_trace=None,
         **kwargs):
    mod = _toy_module()
    cbs = []
    if metric_trace is not None:
        cbs.append(lambda p: metric_trace.append(
            (p.epoch, p.nbatch, dict(p.eval_metric.get_name_value()))))
    user_cb = kwargs.pop("batch_end_callback", None)
    if user_cb is not None:
        cbs.append(user_cb)
    mod.fit(_toy_iter(), num_epoch=EPOCHS, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            eval_metric="acc",
            arg_params=_cp(arg_params), aux_params=_cp(aux_params),
            force_init=arg_params is not None,
            checkpoint_prefix=prefix,
            batch_end_callback=cbs or None, **kwargs)
    return mod


def _params_np(mod):
    arg, aux = mod.get_params()
    return {k: v.asnumpy() for k, v in arg.items()}


def _assert_identical(a, b):
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


# -- iterator-state protocol -----------------------------------------------

def test_dataiter_base_state_protocol_raises():
    it = mxio.DataIter()
    with pytest.raises(NotImplementedError, match="state"):
        it.state_dict()
    with pytest.raises(NotImplementedError):
        it.load_state_dict({})


def test_ndarrayiter_state_roundtrip_and_mismatch():
    x, y = _toy_data()
    it = mxio.NDArrayIter(x, y, batch_size=BATCH)
    it.next()
    it.next()
    st = it.state_dict()
    want = it.next()
    it2 = mxio.NDArrayIter(x, y, batch_size=BATCH)
    it2.load_state_dict(st)
    got = it2.next()
    np.testing.assert_array_equal(want.data[0].asnumpy(),
                                  got.data[0].asnumpy())
    np.testing.assert_array_equal(want.label[0].asnumpy(),
                                  got.label[0].asnumpy())
    bad = mxio.NDArrayIter(x[:32], y[:32], batch_size=BATCH)
    with pytest.raises(MXNetError, match="does not match"):
        bad.load_state_dict(st)


def test_prefetching_iter_state_accounts_for_buffered_batch():
    """The wrapper buffers one produced-but-unconsumed batch; its
    state_dict must describe the CONSUMER position (resume re-produces
    the buffered batch), not the producer's read-ahead."""
    x, y = _toy_data()
    with mxio.PrefetchingIter(
            mxio.NDArrayIter(x, y, batch_size=BATCH)) as it:
        it.next()
        st = it.state_dict()
        want = it.next().data[0].asnumpy()
    with mxio.PrefetchingIter(
            mxio.NDArrayIter(x, y, batch_size=BATCH)) as it2:
        it2.load_state_dict(st)
        got = it2.next().data[0].asnumpy()
    np.testing.assert_array_equal(want, got)


def test_recordio_reader_state_roundtrip(tmp_path):
    path = str(tmp_path / "r.rec")
    w = recordio.MXRecordIO(path, "w")
    payloads = [("rec-%03d" % i).encode() * 7 for i in range(10)]
    for p in payloads:
        w.write(p)
    w.close()
    r = recordio.MXRecordIO(path, "r")
    assert r.read() == payloads[0]
    assert r.read() == payloads[1]
    st = r.state_dict()
    r2 = recordio.MXRecordIO(path, "r")
    r2.load_state_dict(st)
    assert r2.read() == payloads[2]
    with pytest.raises(MXNetError, match="reader"):
        recordio.MXRecordIO(str(tmp_path / "w2.rec"), "w").state_dict()


# -- kill/resume determinism (THE acceptance) -------------------------------

def _kill_and_resume(prefix, kill_at, arg0, aux0, **fit_kw):
    """Arm fit.preempt at batch-hit ``kill_at``, run until preempted,
    then resume — returns (resumed module, metric trace of both legs,
    TrainingPreempted)."""
    trace = []
    faults.arm("fit.preempt", at=kill_at)
    with pytest.raises(TrainingPreempted) as err:
        _fit(prefix, arg_params=arg0, aux_params=aux0,
             metric_trace=trace, **fit_kw)
    faults.disarm()
    assert _no_writer_threads()
    # the preemption left a verified-loadable snapshot behind
    assert err.value.checkpoint_path is not None
    assert os.path.exists(err.value.checkpoint_path)
    # SIGTERM handler restored even though fit raised
    assert signal.getsignal(signal.SIGTERM) in (
        signal.SIG_DFL, signal.default_int_handler)
    mod = _fit(prefix, resume="auto", metric_trace=trace, **fit_kw)
    assert _no_writer_threads()
    return mod, trace, err.value


# kill points: batch 1 of the run, last batch of epoch 0, mid-epoch 1
KILL_POINTS = (1, BATCHES_PER_EPOCH, BATCHES_PER_EPOCH + 2)


@pytest.mark.parametrize("kill_at", KILL_POINTS)
def test_kill_resume_bit_identical(tmp_path, kill_at):
    arg0, aux0 = _init_args()
    ref_trace = []
    ref = _fit(str(tmp_path / "ref"), arg_params=arg0, aux_params=aux0,
               metric_trace=ref_trace, checkpoint_every_n_batches=1)
    res, trace, err = _kill_and_resume(
        str(tmp_path / "victim"), kill_at, arg0, aux0,
        checkpoint_every_n_batches=1)
    _assert_identical(_params_np(ref), _params_np(res))
    # metric trajectory: every batch the resumed leg ran must report the
    # exact value the uninterrupted run reported at that batch (Accuracy
    # sums are integral — float-exact on either path)
    ref_by_pos = {(e, b): v for e, b, v in ref_trace}
    resumed_leg = trace[kill_at:]
    assert resumed_leg, "resumed run produced no batches"
    for e, b, v in resumed_leg:
        assert v == ref_by_pos[(e, b)], (e, b, v, ref_by_pos[(e, b)])
    # both runs end at the same final epoch checkpoint
    assert checkpoint_manifest(str(tmp_path / "victim"))["latest"] == \
        checkpoint_manifest(str(tmp_path / "ref"))["latest"]


@pytest.mark.parametrize("prefetch,nan_policy", [
    (True, None), (False, "skip_batch"), (True, "skip_batch")])
def test_kill_resume_bit_identical_prefetch_and_guard(tmp_path, prefetch,
                                                      nan_policy):
    """The acceptance matrix corners: device-side prefetch double
    buffering and the fused in-graph NaN guard armed."""
    kill_at = BATCHES_PER_EPOCH + 2
    arg0, aux0 = _init_args()
    kw = dict(prefetch_to_device=prefetch, nan_policy=nan_policy,
              checkpoint_every_n_batches=1)
    ref = _fit(str(tmp_path / "ref"), arg_params=arg0, aux_params=aux0,
               **kw)
    res, _trace, _err = _kill_and_resume(
        str(tmp_path / "victim"), kill_at, arg0, aux0, **kw)
    _assert_identical(_params_np(ref), _params_np(res))


def test_kill_resume_with_nan_batch_before_kill(tmp_path):
    """A batch poisoned (and skipped by the guard) BEFORE the kill point
    must not disturb exactness: the skip already happened in the killed
    leg and is part of the snapshot state."""
    arg0, aux0 = _init_args()
    kw = dict(nan_policy="skip_batch", checkpoint_every_n_batches=1)
    faults.arm("fit.batch", at=2)
    ref = _fit(str(tmp_path / "ref"), arg_params=arg0, aux_params=aux0,
               **kw)
    faults.disarm()
    faults.arm("fit.batch", at=2)
    faults.arm("fit.preempt", at=BATCHES_PER_EPOCH + 2)
    with pytest.raises(TrainingPreempted):
        _fit(str(tmp_path / "victim"), arg_params=arg0, aux_params=aux0,
             **kw)
    faults.disarm()
    res = _fit(str(tmp_path / "victim"), resume="auto", **kw)
    _assert_identical(_params_np(ref), _params_np(res))


def test_chaos_kill_resume_matrix(tmp_path):
    """The ci/run_chaos.sh entry point: one kill/resume cycle whose
    dataset, init AND kill point rotate with MXNET_CHAOS_SEED."""
    kill_at = KILL_POINTS[CHAOS_SEED % len(KILL_POINTS)]
    cadence = (CHAOS_SEED % 2) + 1
    arg0, aux0 = _init_args()
    ref = _fit(str(tmp_path / "ref"), arg_params=arg0, aux_params=aux0,
               checkpoint_every_n_batches=cadence)
    res, _trace, _err = _kill_and_resume(
        str(tmp_path / "victim"), kill_at, arg0, aux0,
        checkpoint_every_n_batches=cadence)
    _assert_identical(_params_np(ref), _params_np(res))


def test_signal_during_epoch_end_is_honored(tmp_path):
    """A signal landing during epoch-end processing (checkpoint save,
    callbacks, eval) must not be swallowed: fit drains at the epoch
    BOUNDARY — the completed epoch's checkpoint is the resume point —
    and the resumed run still matches the uninterrupted one."""
    arg0, aux0 = _init_args()
    ref = _fit(str(tmp_path / "ref"), arg_params=arg0, aux_params=aux0)

    def poke(epoch, sym, arg, aux):
        if epoch == 0:
            os.kill(os.getpid(), signal.SIGTERM)

    prefix = str(tmp_path / "victim")
    mod = _toy_module()
    with pytest.raises(TrainingPreempted) as err:
        mod.fit(_toy_iter(), num_epoch=EPOCHS, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                arg_params=_cp(arg0), aux_params=_cp(aux0),
                force_init=True, checkpoint_prefix=prefix,
                epoch_end_callback=poke)
    assert err.value.nbatch is None and err.value.epoch == 0
    assert err.value.checkpoint_path.endswith("-0001.params")
    assert os.path.exists(err.value.checkpoint_path)
    res = _fit(prefix, resume="auto")
    _assert_identical(_params_np(ref), _params_np(res))


def test_corrupt_iter_state_degrades_not_crashes(tmp_path):
    """A snapshot whose iterator state does not fit the resumed
    iterator (different type/shape) must degrade to epoch-boundary
    resume with a warning — the params snapshot is still good."""
    prefix = str(tmp_path / "ck")
    arg0, aux0 = _init_args()
    faults.arm("fit.preempt", at=BATCHES_PER_EPOCH + 2)
    with pytest.raises(TrainingPreempted):
        _fit(prefix, arg_params=arg0, aux_params=aux0,
             checkpoint_every_n_batches=1)
    faults.disarm()
    m = checkpoint_manifest(prefix)
    m["snapshots"][-1]["iter_state"] = \
        {"type": "PrefetchingIter", "inner": [{}, {}]}
    open("%s-manifest.json" % prefix, "w").write(json.dumps(m))
    res = _fit(prefix, resume="auto")  # must not raise
    arg, _ = res.get_params()
    assert all(np.isfinite(v.asnumpy()).all() for v in arg.values())


def test_sigint_also_drains_gracefully(tmp_path):
    arg0, aux0 = _init_args()

    def poke(p):
        if p.epoch == 0 and p.nbatch == 1:
            os.kill(os.getpid(), signal.SIGINT)

    with pytest.raises(TrainingPreempted) as err:
        _fit(str(tmp_path / "v"), arg_params=arg0, aux_params=aux0,
             batch_end_callback=poke)
    assert err.value.signum == signal.SIGINT
    assert err.value.epoch == 0 and err.value.nbatch == 1
    assert telemetry.counter_total("resilience.preemptions") == 1


def test_fit_without_prefix_leaves_signal_handlers_alone():
    """Graceful preemption is tied to checkpointing: a plain fit keeps
    the process's own Ctrl-C / SIGTERM semantics (no handler install,
    no KeyboardInterrupt-semantics change)."""
    arg0, aux0 = _init_args()
    seen = []

    def probe(p):
        seen.append((signal.getsignal(signal.SIGTERM),
                     signal.getsignal(signal.SIGINT)))

    before = (signal.getsignal(signal.SIGTERM),
              signal.getsignal(signal.SIGINT))
    mod = _toy_module()
    mod.fit(_toy_iter(), num_epoch=1, optimizer="sgd",
            arg_params=_cp(arg0), aux_params=_cp(aux0), force_init=True,
            batch_end_callback=probe)
    assert seen and all(s == before for s in seen)


# -- signal-handler hygiene ------------------------------------------------

def test_handlers_restored_after_clean_fit(tmp_path):
    before_term = signal.getsignal(signal.SIGTERM)
    before_int = signal.getsignal(signal.SIGINT)
    arg0, aux0 = _init_args()
    _fit(str(tmp_path / "ck"), arg_params=arg0, aux_params=aux0)
    assert signal.getsignal(signal.SIGTERM) is before_term
    assert signal.getsignal(signal.SIGINT) is before_int


def test_nested_fit_refuses_double_install(tmp_path):
    arg0, aux0 = _init_args()

    def nested(p):
        inner = _toy_module()
        inner.fit(_toy_iter(), num_epoch=1, optimizer="sgd",
                  arg_params=_cp(arg0), aux_params=_cp(aux0),
                  force_init=True,
                  checkpoint_prefix=str(tmp_path / "inner"))

    with pytest.raises(MXNetError, match="double-install"):
        _fit(str(tmp_path / "ck"), arg_params=arg0, aux_params=aux0,
             batch_end_callback=nested)
    # the outer fit's finally released the handlers: a fresh fit works
    _fit(str(tmp_path / "ck2"), arg_params=arg0, aux_params=aux0)
    assert _no_writer_threads()


def test_signal_restore_lint(tmp_path):
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    lint = [sys.executable, "-m", "ci.graftlint", "--pass",
            "signal-restore"]
    assert subprocess.run(lint, cwd=root, timeout=300).returncode == 0
    bad = tmp_path / "bad.py"
    bad.write_text("import signal\n"
                   "def f():\n"
                   "    signal.signal(signal.SIGTERM, None)\n")
    proc = subprocess.run(lint + [str(bad)], capture_output=True,
                          text=True, cwd=root, timeout=300)
    assert proc.returncode == 1
    assert "without a matching restore" in proc.stdout


# -- async writer: back-pressure + lifecycle --------------------------------

def test_async_writer_backpressure_drops_and_joins(tmp_path,
                                                   monkeypatch):
    gate = threading.Event()
    wrote = []

    def slow_write(prefix, snap, logger=None, keep_last=None):
        gate.wait(10)
        wrote.append((snap.epoch, snap.nbatch))
        return "x"

    monkeypatch.setattr(AsyncSnapshotWriter, "_write",
                        lambda self, snap: slow_write(self.prefix, snap))
    from mxnet_tpu.checkpoint import Snapshot

    w = AsyncSnapshotWriter(str(tmp_path / "ck"))
    snap = Snapshot(0, 0, {}, {})
    assert w.submit(snap)
    time.sleep(0.05)  # let the writer pick it up (busy, slot empty)
    assert not w.submit(Snapshot(0, 1, {}, {}))  # dropped: one in flight
    assert telemetry.counter_total(
        "resilience.checkpoint.async_dropped") == 1
    gate.set()
    w.close()
    assert wrote == [(0, 0)]
    assert not w.alive
    assert _no_writer_threads()


def test_writer_error_surfaces_on_fit_exit(tmp_path, monkeypatch):
    def boom(self, snap):
        raise OSError("disk full")

    monkeypatch.setattr(AsyncSnapshotWriter, "_write", boom)
    arg0, aux0 = _init_args()
    with pytest.raises(OSError, match="disk full"):
        _fit(str(tmp_path / "ck"), arg_params=arg0, aux_params=aux0,
             checkpoint_every_n_batches=1)
    assert _no_writer_threads()


# -- sha256 verification + generational fallback ----------------------------

def _corrupt(path):
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF  # same length: only the digest catches it
    open(path, "wb").write(bytes(blob))


def test_resume_skips_corrupt_snapshot_generation(tmp_path):
    os.environ["MXNET_CKPT_ASYNC"] = "0"  # deterministic generation set
    prefix = str(tmp_path / "ck")
    arg0, aux0 = _init_args()
    faults.arm("fit.preempt", at=BATCHES_PER_EPOCH + 2)
    with pytest.raises(TrainingPreempted):
        _fit(prefix, arg_params=arg0, aux_params=aux0,
             checkpoint_every_n_batches=1)
    faults.disarm()
    snaps = checkpoint_manifest(prefix)["snapshots"]
    assert len(snaps) >= 2
    newest = snaps[-1]
    _corrupt(str(tmp_path / newest["params"]))
    st = load_latest_state(prefix)
    assert (st.epoch, st.nbatch) == \
        (snaps[-2]["epoch"], snaps[-2]["nbatch"])
    assert telemetry.counter_total(
        "resilience.checkpoint.corrupt_skipped") == 1


def test_epoch_checkpoint_sha_verified_on_resume(tmp_path):
    prefix = str(tmp_path / "ck")
    arg0, aux0 = _init_args()
    _fit(prefix, arg_params=arg0, aux_params=aux0)
    _corrupt("%s-%04d.params" % (prefix, EPOCHS))
    found = load_latest_checkpoint(prefix)
    assert found is not None and found[0] == EPOCHS - 1
    assert telemetry.counter_total(
        "resilience.checkpoint.corrupt_skipped") >= 1


# -- retention / GC ---------------------------------------------------------

def test_snapshot_retention_gc_glob_unsafe_prefix(tmp_path):
    os.environ["MXNET_CKPT_ASYNC"] = "0"
    os.environ["MXNET_CKPT_KEEP_LAST"] = "2"
    # glob metacharacters in the prefix must not confuse retention/GC
    prefix = str(tmp_path / "ck[1]*x")
    arg0, aux0 = _init_args()
    _fit(prefix, arg_params=arg0, aux_params=aux0,
         checkpoint_every_n_batches=1)
    m = checkpoint_manifest(prefix)
    assert len(m["snapshots"]) == 2
    # every retained generation's payloads exist and verify
    for entry in m["snapshots"]:
        assert os.path.exists(str(tmp_path / entry["params"]))
    # pruned generations are gone: 2*4=8 snapshot ticks, 2 retained
    on_disk = [f for f in os.listdir(str(tmp_path))
               if "-snap-" in f and f.endswith(".params")]
    assert len(on_disk) == 2
    assert telemetry.counter_total("resilience.checkpoint.pruned") > 0


def test_gc_sweeps_orphan_payloads_never_breaks_manifest(tmp_path):
    """Crash-ordering contract: the manifest drops a generation BEFORE
    its files are unlinked, so a crash mid-GC leaves (at worst) orphan
    payloads — which the next GC sweeps — and never a manifest entry
    pointing at removed bytes."""
    os.environ["MXNET_CKPT_ASYNC"] = "0"
    prefix = str(tmp_path / "ck")
    arg0, aux0 = _init_args()
    _fit(prefix, arg_params=arg0, aux_params=aux0,
         checkpoint_every_n_batches=2)
    # simulate the crash: an on-disk snapshot payload not in the manifest
    orphan = snapshot_path(prefix, 7, 123456, "params")
    open(orphan, "wb").write(b"leftover")
    gc_snapshots(prefix, keep_last=1)
    assert not os.path.exists(orphan)
    m = checkpoint_manifest(prefix)
    assert len(m["snapshots"]) == 1
    for entry in m["snapshots"]:
        assert os.path.exists(str(tmp_path / entry["params"]))


def test_fit_validates_batch_cadence(tmp_path):
    arg0, aux0 = _init_args()
    with pytest.raises(MXNetError, match="checkpoint_prefix"):
        _fit(None, arg_params=arg0, aux_params=aux0,
             checkpoint_every_n_batches=1)
    with pytest.raises(MXNetError, match=">= 1"):
        _fit(str(tmp_path / "ck"), arg_params=arg0, aux_params=aux0,
             checkpoint_every_n_batches=0)


def test_fit_preempt_env_spec_parses():
    assert faults.parse_spec("fit.preempt:at=3") == \
        {"fit.preempt": (3, 1)}


def test_env_cadence_ignored_without_prefix():
    """A job-wide MXNET_CKPT_EVERY_N_BATCHES must not break fits that
    never asked for checkpointing; only the explicit argument
    hard-fails."""
    os.environ["MXNET_CKPT_EVERY_N_BATCHES"] = "2"
    arg0, aux0 = _init_args()
    mod = _toy_module()
    mod.fit(_toy_iter(), num_epoch=1, optimizer="sgd",
            arg_params=_cp(arg0), aux_params=_cp(aux0), force_init=True)
    assert _no_writer_threads()


def test_numpy_scalar_metric_state_snapshots_cleanly(tmp_path):
    """CustomMetric fevals routinely return numpy scalars; the snapshot
    manifest json.dumps must not choke on them."""
    os.environ["MXNET_CKPT_ASYNC"] = "0"  # inline: errors surface here
    arg0, aux0 = _init_args()
    metric = mx.metric.CustomMetric(
        lambda label, pred: np.float64(0.5), name="npscalar")
    mod = _toy_module()
    mod.fit(_toy_iter(), num_epoch=1, optimizer="sgd",
            eval_metric=metric,
            arg_params=_cp(arg0), aux_params=_cp(aux0), force_init=True,
            checkpoint_prefix=str(tmp_path / "ck"),
            checkpoint_every_n_batches=1)
    snaps = checkpoint_manifest(str(tmp_path / "ck"))["snapshots"]
    assert snaps and snaps[-1]["metric_state"] is not None


def test_rollback_discards_newer_snapshots(tmp_path):
    """nan_policy='rollback' must prune mid-epoch snapshots from the
    abandoned trajectory, or a later resume='auto' would prefer them
    over the rolled-back-to epoch checkpoint."""
    os.environ["MXNET_CKPT_ASYNC"] = "0"
    prefix = str(tmp_path / "ck")
    arg0, aux0 = _init_args()
    # epoch-1 checkpoint exists; poison the first batch of epoch 1 so
    # rollback restores it — snapshots taken in epoch 1 must vanish
    faults.arm("fit.batch", at=BATCHES_PER_EPOCH + 2)
    _fit(prefix, arg_params=arg0, aux_params=aux0,
         nan_policy="rollback", checkpoint_every_n_batches=1)
    faults.disarm()
    st = load_latest_state(prefix)
    # the newest state is from AFTER the rollback (or the epoch
    # boundary itself), never the pre-rollback poisoned trajectory:
    # resuming from it must yield finite params
    assert st is not None
    for v in st.arg_params.values():
        assert np.isfinite(v.asnumpy()).all()


def test_big_iter_state_goes_to_sidecar(tmp_path):
    """O(dataset) iterator state (shuffled ImageIter permutations) must
    not bloat the manifest — it moves to a sha-verified per-generation
    sidecar."""
    from mxnet_tpu.checkpoint import Snapshot, write_snapshot

    prefix = str(tmp_path / "ck")
    big = {"type": "ImageIter", "cursor": 5,
           "seq": list(range(200000))}
    snap = Snapshot(0, 4, {"w": mx.nd.array(np.ones(3, np.float32))},
                    {}, iter_state=big)
    write_snapshot(prefix, snap)
    m = checkpoint_manifest(prefix)
    entry = m["snapshots"][-1]
    assert entry["iter_state"] is None
    assert entry["iter_state_file"].endswith(".iter.json")
    assert os.path.getsize("%s-manifest.json" % prefix) < 4096
    st = load_latest_state(prefix)
    assert st.iter_state == big
    # a corrupt sidecar fails verification and falls back
    _corrupt(str(tmp_path / entry["iter_state_file"]))
    assert load_latest_state(prefix) is None
    assert telemetry.counter_total(
        "resilience.checkpoint.corrupt_skipped") == 1


# -- serving graceful drain -------------------------------------------------

def test_serving_drain_stops_admitting_and_quiesces():
    from mxnet_tpu import predict  # noqa: F401 — registry deps
    from mxnet_tpu.serving import ModelRegistry, ServingHTTPServer
    import io as _pyio

    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=CLASSES, name="fc"),
        name="softmax")
    rs = np.random.RandomState(0)
    buf = _pyio.BytesIO()
    np.savez(buf, fc_weight=(rs.randn(CLASSES, DIM) * 0.3)
             .astype(np.float32),
             fc_bias=rs.randn(CLASSES).astype(np.float32))
    reg = ModelRegistry(batch_timeout_us=500)
    reg.load("m", net, buf.getvalue(), (DIM,), buckets=(1, 8))
    srv = ServingHTTPServer(reg, port=0).start()
    url = srv.url
    x = rs.rand(2, DIM).astype(np.float32)
    req = urllib.request.Request(
        url + "/predict",
        data=json.dumps({"model": "m", "data": x.tolist()}).encode(),
        headers={"Content-Type": "application/json"})
    assert json.load(urllib.request.urlopen(req, timeout=30))[
        "shape"] == [2, CLASSES]
    # flip draining and observe the admission + readiness behavior
    srv._httpd.draining = True
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req, timeout=30)
    assert e.value.code == 503
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(url + "/healthz", timeout=30)
    assert e.value.code == 503
    assert json.loads(e.value.read())["status"] == "draining"
    srv._httpd.draining = False
    # full drain: quiesces (no pending rows) and stops the listener
    assert srv.drain(deadline=10) is True
    assert srv.draining
    reg.close()
