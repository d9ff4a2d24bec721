"""BaseModule — the abstract training-loop interface.

Reference: ``python/mxnet/module/base_module.py`` (951 LoC; ``fit`` :369,
``score`` :197, ``forward_backward`` :191).  The fit loop is kept
line-compatible in behavior: bind → init_params → init_optimizer → per batch
forward_backward/update/update_metric with callbacks — the call stack in
SURVEY §3.1.
"""

from __future__ import annotations

import contextlib
import logging
import os
import signal as _signal
import threading
import time

import numpy as np

from .. import compile_cache as _compile_cache
from .. import faults as _faults
from .. import metric as _metric
from .. import perfdebug as _perfdebug
from .. import random as _random
from .. import sentinel as _sentinel
from .. import telemetry as _telemetry
from .. import tracing as _tracing
from ..base import MXNetError
from ..elastic import MembershipChanged, StaleEpoch, \
    enabled as _elastic_enabled
from ..model import BatchEndParam
from ..initializer import Uniform

__all__ = ["BaseModule"]

#: control-flow exceptions that hand fit(elastic=True) back to the
#: reshard cycle: a typed stale-epoch rejection from the coordinator, or
#: the batch-boundary membership poll noticing an epoch bump
_ELASTIC_RESYNC = (StaleEpoch, MembershipChanged)

_NAN_POLICIES = ("raise", "skip_batch", "rollback")
#: ``anomaly_policy`` shares the nan_policy vocabulary: a statistical
#: spike is handled exactly like a NaN is (docs/resilience.md
#: "Statistical anomaly rollback")
_ANOMALY_POLICIES = _NAN_POLICIES
_AUDIT_POLICIES = ("raise", "rollback")

#: end-of-iterator sentinel for the phase-timed batch loop (a data batch
#: may legitimately be falsy, so ``None`` would be ambiguous)
_FIT_END = object()

#: resilience counters declared at zero when fit starts under telemetry,
#: so the family is visible in ``snapshot()`` even for a clean run
_RESILIENCE_COUNTERS = (
    "resilience.nan_batches", "resilience.recordio_skipped",
    "resilience.fault_injected", "resilience.checkpoint.saves",
    "resilience.checkpoint.resumes", "resilience.rollbacks",
    "resilience.checkpoint.corrupt_skipped",
    "resilience.checkpoint.async_dropped", "resilience.preemptions")


def _as_metric(m):
    return m if isinstance(m, _metric.EvalMetric) else _metric.create(m)


# -- graceful preemption (docs/resilience.md "Preemption & exact resume") --

#: process-wide owner of the SIGTERM/SIGINT handlers: exactly ONE fit
#: call may hold them — a nested fit (e.g. from a callback) refusing to
#: double-install is the hygiene contract the graftlint signal-restore
#: pass lints the restore half of
_fit_signal_lock = threading.Lock()
_fit_signal_owner = [None]


class _PreemptGuard:
    """Signal-to-flag bridge for one ``fit`` call: the handler only
    records the signal; the batch loop notices at the next boundary,
    finishes the in-flight batch, drains accumulators, checkpoints and
    raises :class:`~mxnet_tpu.checkpoint.TrainingPreempted`.  A SECOND
    signal while draining raises ``KeyboardInterrupt`` immediately — the
    operator insists."""

    __slots__ = ("requested",)

    def __init__(self):
        self.requested = None

    def __call__(self, signum, frame):
        if self.requested is not None:
            raise KeyboardInterrupt(
                "second signal %s during preemption drain" % signum)
        self.requested = signum


@contextlib.contextmanager
def _preempt_signals(guard, logger, enable=True):
    """Install ``guard`` as the SIGTERM/SIGINT handler for the scope,
    restoring the previous handlers on ANY exit path (the try/finally
    is what the graftlint signal-restore pass enforces).  ``enable=False``
    (fit without ``checkpoint_prefix``) leaves the process handlers
    untouched — a plain fit keeps its KeyboardInterrupt semantics.
    Outside the main thread Python forbids handler installation; fit
    then runs without graceful preemption (logged once)."""
    if not enable:
        yield guard
        return
    if threading.current_thread() is not threading.main_thread():
        logger.debug("fit: not on the main thread; SIGTERM/SIGINT "
                     "graceful drain is unavailable here")
        yield guard
        return
    with _fit_signal_lock:
        if _fit_signal_owner[0] is not None:
            raise MXNetError(
                "a fit call already owns the process SIGTERM/SIGINT "
                "handlers (nested fit from a callback?): refusing to "
                "double-install — run the inner fit after the outer one "
                "finishes, or in a separate process")
        _fit_signal_owner[0] = guard
    prev_term = _signal.signal(_signal.SIGTERM, guard)
    try:
        prev_int = _signal.signal(_signal.SIGINT, guard)
        try:
            yield guard
        finally:
            _signal.signal(_signal.SIGINT, prev_int)
    finally:
        _signal.signal(_signal.SIGTERM, prev_term)
        with _fit_signal_lock:
            _fit_signal_owner[0] = None


@contextlib.contextmanager
def _sigquit_dump(logger):
    """Dump-on-demand for the fit scope: SIGQUIT (Ctrl-\\) writes a
    flight-recorder + all-thread-stack dump WITHOUT killing the run —
    the operator's "what is it doing right now" probe for a live job.
    Same installer/finally-restore discipline as :func:`_preempt_signals`
    (the graftlint signal-restore pass lints the restore half); the
    handler only dumps, never raises, so training continues.  Main
    thread only (Python forbids installs elsewhere); a nested fit just
    replaces the outer fit's identical handler and restores it on
    exit."""
    sig = getattr(_signal, "SIGQUIT", None)
    if sig is None or \
            threading.current_thread() is not threading.main_thread():
        yield
        return

    def handler(signum, frame):
        # the dump runs on a SPAWNED thread, not inline: handlers
        # execute between bytecodes of the interrupted frame, which may
        # hold the (non-reentrant) telemetry/flight-recorder locks the
        # dump needs — an inline dump would deadlock the training
        # thread against itself.  Spawn-and-return lets the interrupted
        # frame release its locks, and still works when the training
        # thread is wedged in a C call (the usual reason to probe)
        logger.warning("SIGQUIT: dumping flight recorder + thread "
                       "stacks (run continues)")
        threading.Thread(target=_sentinel.dump_on_demand,
                         args=("sigquit",), name="sigquit-dump",
                         daemon=True).start()

    prev = _signal.signal(sig, handler)
    try:
        yield
    finally:
        _signal.signal(sig, prev)


def _adapt_iter_state(state, target):
    """Bridge an iterator-state capture across a prefetch-wrapping
    difference between the killed and the resumed run: a wrapper state
    unwraps onto a plain iterator (single sub-iterator only) and a plain
    state wraps for a wrapper target."""
    from ..io import PrefetchingIter

    wrapper_state = isinstance(state, dict) and \
        state.get("type") in ("PrefetchingIter", "DevicePrefetchIter")
    if isinstance(target, PrefetchingIter):
        if not wrapper_state:
            return {"type": type(target).__name__, "inner": [state]}
        return state
    if wrapper_state and len(state.get("inner", [])) == 1:
        return state["inner"][0]
    return state


class _FitRun:
    """Per-``fit`` resilience plumbing: the batch-granular snapshot
    cadence, the async writer, the preemption drain sequence, and —
    for ``fit(elastic=True)`` — the elastic ledger-commit/membership-poll
    hooks."""

    def __init__(self, prefix, every_n, writer, guard, logger,
                 keep_last=None, elastic=None):
        self.prefix = prefix
        self.every_n = every_n
        self.writer = writer
        self.guard = guard
        self.logger = logger
        self.keep_last = keep_last
        self.elastic = elastic
        self._warned_iter = False

    def capture(self, module, epoch, nbatch, fit_data, eval_metric):
        """One :class:`~mxnet_tpu.checkpoint.Snapshot`: device copies of
        the big arrays (no host sync), host dicts for the smalls.  The
        metric capture syncs the device-metric accumulator — that IS the
        drain step — and the iterator capture drains the prefetch
        queue."""
        from .. import checkpoint as _ckpt

        if hasattr(module, "_capture_state_arrays"):
            arg, aux, opt_states, opt_counts = \
                module._capture_state_arrays()
        else:
            arg_l, aux_l = module.get_params()
            arg = {k: v.copy() for k, v in arg_l.items()}
            aux = {k: v.copy() for k, v in aux_l.items()}
            opt_states = opt_counts = None
        rng = {"global": _random.get_state()}
        ex = getattr(module, "_exec", None)
        if ex is not None:
            rng["exec_step"] = int(getattr(ex, "_rng_step", 0))
        try:
            iter_state = fit_data.state_dict()
        except NotImplementedError:
            if not self._warned_iter:
                self.logger.warning(
                    "checkpoint snapshot: %s has no iterator-state "
                    "protocol; mid-epoch resume will degrade to the "
                    "epoch boundary", type(fit_data).__name__)
                self._warned_iter = True
            iter_state = None
        try:
            metric_state = eval_metric.get_state()
        except NotImplementedError:
            metric_state = None
        mesh_info = None
        get_info = getattr(module, "_snapshot_mesh_info", None)
        if callable(get_info):
            # kvstore='mesh' with world > 1: the generation writes as
            # per-shard payload files + a stitching manifest entry
            mesh_info = get_info()
        snap = _ckpt.Snapshot(epoch, nbatch, arg, aux,
                              opt_states=opt_states,
                              opt_counts=opt_counts, rng_state=rng,
                              metric_state=metric_state,
                              iter_state=iter_state,
                              mesh_info=mesh_info)
        if self.elastic is not None:
            # fold the coordinator-side optimizer states in: elastic
            # rehydration restores the server's momentum from the snapshot
            self.elastic.augment_snapshot(snap)
        return snap

    def after_batch(self, module, epoch, nbatch, fit_data, eval_metric,
                    drain_guard=None, data_batch=None):
        """Bottom-of-batch hook: commit the batch to the elastic data
        ledger, take the cadence snapshot, honor a pending preemption
        (the in-flight batch is complete by now), then poll elastic
        membership — a change raises out to the reshard cycle."""
        if self.elastic is not None:
            self.elastic.commit(data_batch)
        if self.every_n is not None and (nbatch + 1) % self.every_n == 0 \
                and (self.elastic is None or self.elastic.is_leader()):
            # elastic fits share one prefix across ranks: only the
            # membership leader writes, so generations never interleave
            self.writer.submit(
                self.capture(module, epoch, nbatch, fit_data, eval_metric))
        self.check_preempt(module, epoch, nbatch, fit_data, eval_metric,
                           drain_guard)
        if self.elastic is not None:
            self.elastic.poll(epoch, nbatch)

    def epoch_end_preempt(self, module, epoch, already_saved):
        """Preemption noticed at the epoch boundary: epoch ``epoch`` is
        fully complete (metrics logged, eval done, iterator reset), so
        the resume point is the epoch-``epoch + 1`` checkpoint — written
        here if the cadence had not already produced it."""
        from .. import checkpoint as _ckpt

        signum = self.guard.requested
        path = None
        if self.prefix is not None and \
                (self.elastic is None or self.elastic.is_leader()):
            # elastic ranks share one prefix: only the membership leader
            # writes the drain checkpoint (same single-writer rule as the
            # cadence snapshots); a preempted non-leader just leaves — the
            # survivors reshard from the leader's generations
            if not already_saved:
                arg_params_, aux_params_ = module.get_params()
                module._save_fit_checkpoint(self.prefix, epoch + 1,
                                            arg_params_, aux_params_)
            path = "%s-%04d.params" % (self.prefix, epoch + 1)
        _telemetry.inc("resilience.preemptions")
        _telemetry.event("preemption", epoch=epoch, nbatch=None,
                         signal=signum, checkpoint=path)
        _perfdebug.flight_dump("preemption", epoch=epoch, nbatch=None,
                               signal=signum, checkpoint=path)
        self.logger.warning(
            "preempted (signal %s) during epoch %d wrap-up: epoch "
            "complete, checkpoint %s", signum, epoch,
            path if path else "skipped (no checkpoint_prefix)")
        raise _ckpt.TrainingPreempted(
            "training preempted by signal %s at the end of epoch %d "
            "(epoch complete; resume with resume='auto')"
            % (signum, epoch), checkpoint_path=path, epoch=epoch,
            nbatch=None, signum=signum)

    def check_preempt(self, module, epoch, nbatch, fit_data, eval_metric,
                      drain_guard=None):
        from .. import checkpoint as _ckpt

        if self.guard is None or self.guard.requested is None:
            return
        signum = self.guard.requested
        # drain order: NaN-guard flag first (a poisoned final batch must
        # not be checkpointed unflagged), then the capture itself syncs
        # the device-metric accumulator and the prefetch queue
        if drain_guard is not None:
            drain_guard()
        path = None
        if self.prefix is not None and \
                (self.elastic is None or self.elastic.is_leader()):
            # single-writer rule under a shared elastic prefix (see
            # epoch_end_preempt): a preempted non-leader writes nothing —
            # concurrent same-generation writes from racing ranks could
            # interleave params/states files across writers
            snap = self.capture(module, epoch, nbatch, fit_data,
                                eval_metric)
            if self.writer is not None:
                # wait out an in-flight async write (≤1 by construction),
                # then write the final snapshot synchronously.  A STALE
                # background-write failure must not abort the drain —
                # the final snapshot below is exactly what a preempted
                # worker needs most
                try:
                    self.writer.drain()
                except Exception as e:  # noqa: broad-except — logged;
                    # the synchronous final write raises its own errors
                    self.logger.warning(
                        "preemption drain: earlier async snapshot write "
                        "had failed (%s); writing the final snapshot "
                        "anyway", e)
            path = _ckpt.write_snapshot(self.prefix, snap,
                                        logger=self.logger,
                                        keep_last=self.keep_last)
        _telemetry.inc("resilience.preemptions")
        _telemetry.event("preemption", epoch=epoch, nbatch=nbatch,
                         signal=signum, checkpoint=path)
        # the post-mortem record: last batches' phase timings, compiled-
        # executable attribution and the preemption event itself survive
        # the process (docs/observability.md "Flight recorder")
        _perfdebug.flight_dump("preemption", epoch=epoch, nbatch=nbatch,
                               signal=signum, checkpoint=path)
        self.logger.warning(
            "preempted (signal %s) at epoch %d batch %d: in-flight batch "
            "finished, accumulators drained, checkpoint %s",
            signum, epoch, nbatch,
            path if path else "skipped (no checkpoint_prefix)")
        raise _ckpt.TrainingPreempted(
            "training preempted by signal %s at epoch %d batch %d "
            "(graceful drain complete; resume with resume='auto')"
            % (signum, epoch, nbatch), checkpoint_path=path, epoch=epoch,
            nbatch=nbatch, signum=signum)


class BaseModule:
    """reference ``base_module.py:56``"""

    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # -- high-level API ---------------------------------------------------
    def forward_backward(self, data_batch):
        """reference :191"""
        self.forward(data_batch, is_train=True)
        self.backward()

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0):
        """reference :197 — like ``fit``, auto-selects the device metric
        path for eligible metrics (the wrapped metric object the caller
        passed is folded back into at the final sync, so its ``get()``
        stays correct)."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        # wrap BEFORE reset: a cached device wrapper may hold unsynced
        # stats from a previous pass, and reset() clears both layers
        eval_metric = _metric.as_device(_as_metric(eval_metric))
        eval_metric.reset()
        actual_num_batch = 0
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            # score emits no telemetry phases: tick the hang watchdog's
            # liveness clock so a long validation pass inside an armed
            # fit never reads as a wedged step (free when unarmed)
            _sentinel.note_progress()
            self.forward(eval_batch, is_train=False)
            self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                batch_end_param = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                                eval_metric=eval_metric,
                                                locals=locals())
                for callback in _as_list(batch_end_callback):
                    callback(batch_end_param)
            actual_num_batch += 1
        if score_end_callback:
            params = BatchEndParam(epoch=epoch, nbatch=actual_num_batch,
                                   eval_metric=eval_metric, locals=locals())
            for callback in _as_list(score_end_callback):
                callback(params)
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        """reference base_module.py iter_predict"""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad] for out in self.get_outputs()]
            yield (outputs, nbatch, eval_batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        """reference base_module.py predict"""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad].copy()
                       for out in self.get_outputs()]
            output_list.append(outputs)
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                assert len(out) == num_outputs, \
                    "Cannot merge batches: mismatched output count"
            from ..ndarray import concatenate

            output_list2 = [concatenate([out[i] for out in output_list])
                            for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=Uniform(0.01), arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, checkpoint_prefix=None, checkpoint_period=1,
            resume=None, nan_policy=None, nan_check_period=None,
            prefetch_to_device=None, checkpoint_every_n_batches=None,
            elastic=None, anomaly_policy=None,
            audit_every_n_batches=None):
        """reference ``base_module.py:369`` — THE training loop.

        Sync-free hot loop (docs/how_to/perf.md): eligible metrics are
        auto-wrapped in :class:`~mxnet_tpu.metric.DeviceMetric` (per-batch
        stats accumulate on device; reads at callback cadence are the only
        syncs — ``MXNET_DEVICE_METRIC=0`` restores the host path), the
        NaN guard is folded into the step as one in-graph reduction read
        every ``nan_check_period`` batches (``MXNET_NAN_CHECK_PERIOD``,
        default 1), and ``prefetch_to_device=True``
        (``MXNET_DEVICE_PREFETCH=1``) stages each batch's H2D copy on a
        background thread via :class:`~mxnet_tpu.io.DevicePrefetchIter`.

        Resilience extensions (docs/resilience.md):

        ``checkpoint_prefix``
            When set, an atomic checkpoint (params [+ optimizer states] +
            manifest) is written every ``checkpoint_period`` epochs and at
            the final epoch.  Additionally installs SIGTERM/SIGINT
            graceful-preemption handlers for the duration of the call
            (restored on exit): on signal the in-flight batch finishes,
            accumulators drain, a final mid-epoch snapshot is written and
            :class:`~mxnet_tpu.checkpoint.TrainingPreempted` is raised
            carrying the checkpoint path.
        ``checkpoint_every_n_batches``
            Batch-granular snapshot cadence (default: the
            ``MXNET_CKPT_EVERY_N_BATCHES`` env var; unset disables).
            Every N batches the params / optimizer states are captured as
            device-side copies (no host sync on the hot loop) and a
            background writer thread serializes them — manifest-last,
            sha256-recorded, ``MXNET_CKPT_KEEP_LAST`` generations
            retained (``MXNET_CKPT_ASYNC=0`` forces inline writes).  At
            most one snapshot is ever in flight; cadence ticks landing on
            a busy writer are dropped and counted.
        ``resume="auto"``
            Restart from the newest checkpoint OR mid-epoch snapshot
            under ``checkpoint_prefix`` that passes sha256 + load
            verification; corrupt generations are skipped with a warning
            and counted.  A mid-epoch snapshot resumes EXACTLY: params,
            optimizer states and update counts, RNG streams, metric
            accumulators and the data-iterator position are restored, so
            the resumed trajectory is bit-identical to an uninterrupted
            run (tests/test_preemption.py pins this).
        ``nan_policy``
            Per-batch NaN/Inf guard on loss and gradients (default: the
            ``MXNET_NAN_POLICY`` env var; None disables).  ``"raise"``
            aborts with MXNetError, ``"skip_batch"`` drops the batch's
            update, ``"rollback"`` restores the last valid checkpoint and
            drops the batch.  Tripped batches are visible to callbacks via
            ``BatchEndParam.nan_detected``/``nan_action``.  The check is a
            device-side reduction folded into the step; with
            ``nan_check_period=N`` the one-scalar flag read happens every
            N batches (amortized semantics: see docs/resilience.md).
        ``anomaly_policy``
            (default: the ``MXNET_ANOMALY_POLICY`` env var; None
            disables)  Statistical anomaly guard generalizing
            ``nan_policy``: the global gradient norm of every batch is
            z-scored against a rolling window
            (``MXNET_ANOMALY_WINDOW`` batches,
            ``MXNET_ANOMALY_ZSCORE`` sigmas) and a finite spike trips
            the same raise / skip_batch / rollback vocabulary — a loss
            explosion is handled like a NaN is today, BEFORE the
            poisoned update lands.  skip/rollback trips are bounded by
            the consecutive ``MXNET_ROLLBACK_BUDGET`` (exhaustion
            raises :class:`~mxnet_tpu.sentinel.AnomalyBudgetExhausted`).
            Costs one scalar read per batch, and a staged fused step is
            materialized two-phase (gradients must be inspectable) —
            like ``monitor``, this is a diagnosis-over-fusion trade.
        ``audit_every_n_batches``
            (default: the ``MXNET_AUDIT_EVERY_N_BATCHES`` env var;
            unset disables)  Cross-replica integrity audits for
            ``kvstore='mesh'`` fits: every N batches ONE extra jitted
            program folds per-param bit-pattern checksums per mesh
            replica and compares them in-graph (replicated state must
            agree EXACTLY; ZeRO-owned rows are covered post-gather
            through the params they re-enter) — one tiny host read.
            A mismatch emits ``reliability.divergence`` and, per
            ``MXNET_AUDIT_POLICY``, raises
            :class:`~mxnet_tpu.sentinel.ReplicaDivergence` or rolls
            back to the last good checkpoint.
        ``MXNET_WATCHDOG=1`` (env)
            Arms the hang watchdog for the duration of the call: a
            sentinel thread tracks per-batch progress against a
            deadline auto-calibrated from the rolling median step time
            and, on expiry, dumps the flight recorder + all-thread
            stacks and raises
            :class:`~mxnet_tpu.sentinel.TrainingWedged` in this thread
            (``MXNET_WATCHDOG_ACTION``: raise/warn/exit) instead of
            hanging forever.  Also maintains the
            ``MXNET_HEARTBEAT_FILE`` heartbeat ``tools/supervise.py``
            watches.  SIGQUIT during any fit writes the same dump
            without killing the run.
        ``elastic``
            (default: the ``MXNET_ELASTIC`` env var) Elastic membership
            (docs/resilience.md "Elastic membership & resharding"): the
            world size may change mid-job.  Requires a ``dist_*``
            kvstore and ``checkpoint_prefix`` (the snapshot protocol is
            the reshard transport; the cadence is PINNED to every batch
            — a sparser ``checkpoint_every_n_batches`` is a typed error
            and ``MXNET_CKPT_EVERY_N_BATCHES`` is ignored with a
            warning, because the manifest is the reshard rollback
            target and a sparser cadence would discard committed work).
            On a membership-epoch bump this fit quiesces at the next
            batch boundary, rendezvouses with the surviving/new members,
            rehydrates from the newest snapshot generation and continues
            in-loop — the process never restarts, and two replays of the
            same elasticity schedule are bit-identical.  Pair
            ``train_data`` with an :class:`~mxnet_tpu.io.ElasticShardIter`
            so the data partition reshards with the world.  NOTE: the
            initial rendezvous also adopts the newest snapshot
            generation already under ``checkpoint_prefix`` — a mid-job
            joiner is indistinguishable from a fresh start, so
            ``elastic=True`` implies ``resume="auto"`` semantics; give
            a fresh job a fresh prefix.
        """
        assert num_epoch is not None, "please specify number of epochs"

        if elastic is None:
            elastic = _elastic_enabled()
        if elastic:
            if checkpoint_prefix is None:
                raise MXNetError(
                    "fit(elastic=True) needs checkpoint_prefix: the "
                    "snapshot manifest is the reshard transport")
            # elastic rollback granularity IS the snapshot cadence, and
            # it is pinned to every batch: a sparser cadence would
            # discard up to N-1 committed batches per membership change
            # and widen the no-generation reshard window the ledger
            # fallback is built around (io.py ElasticShardIter.reshard)
            if checkpoint_every_n_batches is not None \
                    and checkpoint_every_n_batches > 1:
                raise MXNetError(
                    "fit(elastic=True) snapshots every batch (the "
                    "manifest is the reshard rollback target); got "
                    "checkpoint_every_n_batches=%d"
                    % checkpoint_every_n_batches)
            env_n = int(os.environ.get(
                "MXNET_CKPT_EVERY_N_BATCHES", "0") or 0)
            if env_n > 1:
                self.logger.warning(
                    "MXNET_CKPT_EVERY_N_BATCHES=%d ignored under "
                    "fit(elastic=True): elastic snapshots every batch "
                    "(the manifest is the reshard rollback target)",
                    env_n)
            checkpoint_every_n_batches = 1

        if nan_policy is None:
            nan_policy = os.environ.get("MXNET_NAN_POLICY") or None
        if nan_policy is not None and nan_policy not in _NAN_POLICIES:
            raise MXNetError("nan_policy must be one of %s, got %r"
                             % (_NAN_POLICIES, nan_policy))
        if nan_check_period is None:
            nan_check_period = int(
                os.environ.get("MXNET_NAN_CHECK_PERIOD", "1") or 1)
        if nan_check_period < 1:
            raise MXNetError("nan_check_period must be >= 1, got %r"
                             % (nan_check_period,))
        if prefetch_to_device is None:
            prefetch_to_device = os.environ.get(
                "MXNET_DEVICE_PREFETCH", "0") not in ("0", "", "false")
        if nan_policy == "rollback" and checkpoint_prefix is None:
            raise MXNetError(
                "nan_policy='rollback' needs checkpoint_prefix to know "
                "what to roll back to")
        if anomaly_policy is None:
            anomaly_policy = os.environ.get("MXNET_ANOMALY_POLICY") or None
        if anomaly_policy is not None \
                and anomaly_policy not in _ANOMALY_POLICIES:
            raise MXNetError("anomaly_policy must be one of %s, got %r"
                             % (_ANOMALY_POLICIES, anomaly_policy))
        if anomaly_policy == "rollback" and checkpoint_prefix is None:
            raise MXNetError(
                "anomaly_policy='rollback' needs checkpoint_prefix to "
                "know what to roll back to")
        if audit_every_n_batches is None:
            audit_every_n_batches = int(os.environ.get(
                "MXNET_AUDIT_EVERY_N_BATCHES", "0") or 0) or None
        if audit_every_n_batches is not None \
                and audit_every_n_batches < 1:
            raise MXNetError(
                "audit_every_n_batches must be >= 1, got %r"
                % (audit_every_n_batches,))
        audit_policy = os.environ.get("MXNET_AUDIT_POLICY") or "raise"
        if audit_policy not in _AUDIT_POLICIES:
            raise MXNetError("MXNET_AUDIT_POLICY must be one of %s, "
                             "got %r" % (_AUDIT_POLICIES, audit_policy))
        if audit_every_n_batches is not None \
                and audit_policy == "rollback" \
                and checkpoint_prefix is None:
            raise MXNetError(
                "MXNET_AUDIT_POLICY='rollback' needs checkpoint_prefix "
                "to know what to roll back to")
        if resume not in (None, "auto"):
            raise MXNetError("resume must be None or 'auto', got %r"
                             % (resume,))
        if checkpoint_prefix is not None and checkpoint_period < 1:
            raise MXNetError("checkpoint_period must be >= 1, got %r"
                             % (checkpoint_period,))
        if checkpoint_every_n_batches is None:
            env_cadence = int(os.environ.get(
                "MXNET_CKPT_EVERY_N_BATCHES", "0") or 0) or None
            if env_cadence is not None and checkpoint_prefix is None:
                # a job-wide env cadence must not break fits that never
                # asked for checkpointing; only the EXPLICIT argument
                # hard-fails below
                self.logger.debug(
                    "MXNET_CKPT_EVERY_N_BATCHES=%d ignored: this fit "
                    "has no checkpoint_prefix", env_cadence)
            else:
                checkpoint_every_n_batches = env_cadence
        if checkpoint_every_n_batches is not None:
            if checkpoint_prefix is None:
                raise MXNetError(
                    "checkpoint_every_n_batches needs checkpoint_prefix")
            if checkpoint_every_n_batches < 1:
                raise MXNetError(
                    "checkpoint_every_n_batches must be >= 1, got %r"
                    % (checkpoint_every_n_batches,))
        resume_states = None
        resume_state = None  # mid-epoch TrainingState (exact resume)
        if resume == "auto":
            if checkpoint_prefix is None:
                raise MXNetError("resume='auto' needs checkpoint_prefix")
            from ..checkpoint import load_latest_state

            found = load_latest_state(checkpoint_prefix,
                                      logger=self.logger)
            if found is not None:
                _telemetry.inc("resilience.checkpoint.resumes")
                _telemetry.event("checkpoint.resume", epoch=found.epoch,
                                 nbatch=found.nbatch,
                                 prefix=checkpoint_prefix)
                begin_epoch = found.epoch
                arg_params, aux_params = \
                    found.arg_params, found.aux_params
                force_init = True
                if found.nbatch is None:
                    if found.states_path is not None \
                            and hasattr(self, "load_optimizer_states"):
                        resume_states = found.states_path
                    self.logger.info(
                        "resume='auto': restarting from checkpoint epoch "
                        "%d (%s)", found.epoch, checkpoint_prefix)
                else:
                    resume_state = found
                    self.logger.info(
                        "resume='auto': exact mid-epoch resume from "
                        "snapshot epoch %d batch %d (%s)", found.epoch,
                        found.nbatch, checkpoint_prefix)
            else:
                self.logger.info(
                    "resume='auto': no loadable checkpoint under %r; "
                    "starting from scratch", checkpoint_prefix)

        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label, for_training=True,
                  force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)
        if resume_states is not None:
            self.load_optimizer_states(resume_states)
        if resume_state is not None:
            # exact resume: optimizer states + update counts + RNG
            # streams (the iterator position is restored further down,
            # once the actual fit iterator — wrapper included — exists)
            if hasattr(self, "_restore_opt_snapshot"):
                self._restore_opt_snapshot(resume_state.states_bytes,
                                           resume_state.opt_counts)
            rng = resume_state.rng_state or {}
            if rng.get("global"):
                _random.set_state(rng["global"])
            ex = getattr(self, "_exec", None)
            if ex is not None and rng.get("exec_step") is not None:
                ex._rng_step = int(rng["exec_step"])
        if resume == "auto" and _compile_cache.enabled() \
                and hasattr(self, "warm_from_manifest"):
            # compile-once warm-up (docs/how_to/perf.md "Compile once"):
            # replay the manifest the previous run saved next to its
            # checkpoints, so every executable is pre-built — pure
            # persistent-cache loads — before the loop restarts.  AOT
            # only: nothing executes, exact-resume state is untouched.
            man = _compile_cache.load_manifest(
                _compile_cache.manifest_path(checkpoint_prefix))
            if man is not None:
                try:
                    self.warm_from_manifest(man)
                except Exception as e:  # noqa: broad-except — warm-up
                    # is an optimization; resume must proceed without it
                    self.logger.warning(
                        "compile_cache: warm-up manifest replay failed "
                        "(%s: %s); executables will compile lazily",
                        type(e).__name__, e)
        if hasattr(self, "_install_nan_guard"):
            # unconditional: a previous fit's guard must DISARM when this
            # fit runs without a policy (stale accumulated flags would
            # otherwise leak into a later guarded run)
            self._install_nan_guard(nan_policy)
        for pol_name, pol in (("nan_policy", nan_policy),
                              ("anomaly_policy", anomaly_policy)):
            if pol not in ("skip_batch", "rollback"):
                continue
            kv = getattr(self, "_kvstore", None)
            if kv is not None and getattr(kv, "num_workers", 1) > 1 \
                    and not getattr(kv, "in_graph_sync", False):
                # the NaN/anomaly check sees only this rank's
                # loss/grads (the anomaly z-score even judges against
                # rank-LOCAL history), and skipping update() skips this
                # rank's PS push — the other ranks still push, so sync
                # rounds shift one step out of phase (and 'rollback'
                # restores params on one rank only)
                self.logger.warning(
                    "%s=%r is rank-local: skipping a batch in "
                    "multi-worker sync training desynchronizes parameter-"
                    "server rounds across ranks; prefer %s='raise' "
                    "with resume='auto' for distributed runs",
                    pol_name, pol, pol_name)
        if validation_metric is None:
            validation_metric = eval_metric
        # materialize the validation metric ONCE so every epoch's score()
        # reuses one (device-wrapped) instance and its jit cache, instead
        # of re-creating + retracing per epoch
        validation_metric = _metric.as_device(_as_metric(validation_metric))
        eval_metric = _metric.as_device(_as_metric(eval_metric))

        # MXNET_BULK_TRAIN_STEPS=K dispatches K steps per XLA program
        # (Module.run_bulk lax.scan) — the training-loop spelling of the
        # reference's MXNET_EXEC_BULK_EXEC_TRAIN op bulking.  Metric
        # updates and batch callbacks still fire per batch (from the
        # scanned outputs); monitors need per-step observation, so a
        # monitor forces the classic path — as do the per-batch NaN guard
        # and the fit.batch fault point, which must see every step.
        bulk_k = max(1, int(os.environ.get("MXNET_BULK_TRAIN_STEPS", "1")))
        # the fit.preempt fault ("deliver SIGTERM at batch k") needs the
        # per-batch loop for deterministic batch-k delivery, like
        # fit.batch does
        # the sentinel's per-batch detectors (anomaly z-score, integrity
        # audit cadence, the fit.wedge fault) need the per-batch loop —
        # a scanned chunk has no batch boundaries to observe at
        use_bulk = bulk_k > 1 and monitor is None \
            and nan_policy is None and anomaly_policy is None \
            and audit_every_n_batches is None \
            and not _faults.armed("fit.batch") \
            and not _faults.armed("fit.preempt") \
            and not _faults.armed("fit.wedge") \
            and not elastic and hasattr(self, "run_bulk")
        if use_bulk and hasattr(self, "_full_step_eligible") \
                and not self._full_step_eligible():
            self.logger.warning(
                "MXNET_BULK_TRAIN_STEPS=%d has no effect: the fused step "
                "is not eligible (requires MXNET_FUSE_TRAIN_STEP=1, plain "
                "SGD, local/in-graph kvstore); training runs per batch",
                bulk_k)

        if _telemetry.enabled():
            # declare the resilience family at zero so a clean run's
            # snapshot still shows it (docs/observability.md)
            _telemetry.declare(*_RESILIENCE_COUNTERS)

        def _trip_nan_policy(epoch, nbatch, gated):
            """Apply ``nan_policy`` to a flagged batch.  ``gated``: the
            fused step already withheld the non-finite update in-graph."""
            _telemetry.inc("resilience.nan_batches", action=nan_policy)
            _telemetry.event("nan_batch", epoch=epoch, batch=nbatch,
                             action=nan_policy)
            _perfdebug.flight_dump("nan_trip", epoch=epoch, nbatch=nbatch,
                                   action=nan_policy)
            if nan_policy == "raise":
                raise MXNetError(
                    "NaN/Inf detected in loss/gradients at epoch %d "
                    "batch %d (nan_policy='raise')" % (epoch, nbatch))
            if nan_policy == "rollback":
                self.logger.warning(
                    "NaN/Inf at epoch %d batch %d: rolling back to the "
                    "last valid checkpoint", epoch, nbatch)
                self._rollback_to_checkpoint(checkpoint_prefix)
            elif gated:
                self.logger.warning(
                    "NaN/Inf at epoch %d batch %d: batch update withheld "
                    "in-graph (skip_batch)", epoch, nbatch)
            else:
                self.logger.warning(
                    "NaN/Inf at epoch %d batch %d: skipping batch",
                    epoch, nbatch)

        anomaly_detector = None
        anomaly_budget = None
        anomaly_consec = [0]  # consecutive skip/rollback trips
        if anomaly_policy is not None:
            anomaly_detector = _sentinel.AnomalyDetector()
            anomaly_budget = int(os.environ.get(
                "MXNET_ROLLBACK_BUDGET", "3") or 3)
            _telemetry.declare("reliability.anomalies")

        def _trip_anomaly(epoch, nbatch, value):
            """Apply ``anomaly_policy`` to a z-score-flagged batch whose
            update was WITHHELD (the grad-norm read happens before
            ``update()``)."""
            _telemetry.inc("reliability.anomalies", action=anomaly_policy)
            _telemetry.event("reliability.anomaly", epoch=epoch,
                             batch=nbatch, action=anomaly_policy,
                             grad_norm=value)
            _perfdebug.flight_dump("anomaly", epoch=epoch, nbatch=nbatch,
                                   action=anomaly_policy, grad_norm=value)
            if anomaly_policy == "raise":
                raise MXNetError(
                    "gradient-norm anomaly (%.4g) at epoch %d batch %d "
                    "(anomaly_policy='raise')" % (value, epoch, nbatch))
            anomaly_consec[0] += 1
            if anomaly_consec[0] > anomaly_budget:
                raise _sentinel.AnomalyBudgetExhausted(
                    "anomaly_policy=%r tripped on %d consecutive batches "
                    "(MXNET_ROLLBACK_BUDGET=%d): the spike is not "
                    "transient — refusing to %s forever"
                    % (anomaly_policy, anomaly_consec[0], anomaly_budget,
                       anomaly_policy))
            if anomaly_policy == "rollback":
                self.logger.warning(
                    "gradient-norm anomaly (%.4g) at epoch %d batch %d: "
                    "rolling back to the last valid checkpoint and "
                    "skipping the batch (%d/%d consecutive)",
                    value, epoch, nbatch, anomaly_consec[0],
                    anomaly_budget)
                self._rollback_to_checkpoint(checkpoint_prefix)
            else:
                self.logger.warning(
                    "gradient-norm anomaly (%.4g) at epoch %d batch %d: "
                    "skipping batch (%d/%d consecutive)",
                    value, epoch, nbatch, anomaly_consec[0],
                    anomaly_budget)

        # device-side double-buffered prefetch: a background thread runs
        # each batch's host→device copy (honoring the module's sharding
        # via _device_put_batch) so H2D overlaps the previous step's
        # compute — the device-level completion of PrefetchingIter's
        # host-decode overlap (iter_prefetcher.h analog)
        fit_data = train_data
        if prefetch_to_device and hasattr(self, "_device_put_batch") \
                and not getattr(self, "_dist_dp", False):
            from ..io import DevicePrefetchIter

            fit_data = DevicePrefetchIter(train_data,
                                          placer=self._device_put_batch)
        owns_iter = fit_data is not train_data
        # exact mid-epoch resume: the iterator position restores onto the
        # iterator fit actually drives (the prefetch wrapper when owned —
        # its restore drains the queue and rewinds the inner iterator)
        resume_nbatch = None
        resume_metric_state = None
        if resume_state is not None and resume_state.nbatch is not None:
            if resume_state.iter_state is not None:
                try:
                    fit_data.load_state_dict(_adapt_iter_state(
                        resume_state.iter_state, fit_data))
                    resume_nbatch = resume_state.nbatch
                    resume_metric_state = resume_state.metric_state
                except Exception as e:  # noqa: broad-except — ANY
                    # restore failure (unsupported iterator, a snapshot
                    # from a different iterator type raising KeyError,
                    # shape mismatch) must degrade to epoch-boundary
                    # resume, never abort a fit whose params snapshot
                    # loaded fine
                    self.logger.warning(
                        "resume: could not restore the iterator position "
                        "(%s: %s); restarting epoch %d from batch 0 — "
                        "data from the partial epoch will replay",
                        type(e).__name__, e, resume_state.epoch)
            else:
                self.logger.warning(
                    "resume: snapshot carries no iterator state; "
                    "restarting epoch %d from batch 0 — data from the "
                    "partial epoch will replay", resume_state.epoch)
        elastic_run = None
        if elastic:
            from ..elastic import ElasticFitRun

            kv = getattr(self, "_kvstore", None)
            if kv is None or not hasattr(kv, "reshard_sync"):
                raise MXNetError(
                    "fit(elastic=True) needs a dist_* kvstore (got %r): "
                    "elastic membership lives on the KVStore coordinator"
                    % (kvstore if kv is None else kv.type))
            elastic_run = ElasticFitRun(self, kv, checkpoint_prefix,
                                        fit_data, self.logger)
            _telemetry.declare("elastic.resharded.count",
                               "elastic.stale_epoch.count")
        writer = None
        if checkpoint_every_n_batches is not None:
            from ..checkpoint import AsyncSnapshotWriter

            # elastic snapshots are the reshard rollback target: they
            # must exist deterministically at every committed boundary,
            # so the writer is PINNED inline (the async writer drops
            # cadence snapshots when busy, which would make the rollback
            # generation timing-dependent and break replay bit-identity)
            # — an explicit MXNET_CKPT_ASYNC=1 is ignored with a warning,
            # the same treatment MXNET_CKPT_EVERY_N_BATCHES gets
            ckpt_async = os.environ.get(
                "MXNET_CKPT_ASYNC", "0" if elastic else "1") \
                not in ("0", "", "false")
            if elastic and ckpt_async:
                self.logger.warning(
                    "MXNET_CKPT_ASYNC=1 ignored under fit(elastic=True): "
                    "elastic snapshots are the reshard rollback target "
                    "and must land inline at every committed boundary")
                ckpt_async = False
            writer = AsyncSnapshotWriter(checkpoint_prefix,
                                         logger=self.logger,
                                         sync=not ckpt_async)
        guard = _PreemptGuard()
        run = _FitRun(checkpoint_prefix, checkpoint_every_n_batches,
                      writer, guard, self.logger, elastic=elastic_run)
        # visible to _rollback_to_checkpoint: a rollback must quiesce
        # the writer before discarding post-rollback snapshots
        self._active_ckpt_writer = writer
        watchdog = None
        if _sentinel.watchdog_enabled():
            # the hang watchdog arms for exactly this fit's duration;
            # start() runs HERE so the injection target is this thread
            watchdog = _sentinel.Watchdog(logger=self.logger)
        try:
            # graceful preemption is tied to checkpointing: a fit that
            # never asked for a checkpoint_prefix keeps the process's
            # own SIGTERM/SIGINT semantics (Ctrl-C still interrupts);
            # the SIGQUIT dump-on-demand probe is unconditional
            with _sigquit_dump(self.logger), \
                    _preempt_signals(guard, self.logger,
                                     enable=checkpoint_prefix is not None):
                if watchdog is not None:
                    watchdog.start()
                try:
                    if elastic_run is not None:
                        # initial rendezvous: adopt the membership epoch
                        # and world, shard the data service — and, for a
                        # mid-job JOINER, rehydrate from the running
                        # job's newest snapshot generation
                        begin_epoch, resume_nbatch, resume_metric_state \
                            = elastic_run.sync(
                                (begin_epoch, resume_nbatch,
                                 resume_metric_state))
                    while True:
                        try:
                            # a batch that raises in the middle of its
                            # span leaves the frame to end it
                            with _tracing.frame():
                                self._fit_epochs(
                                    fit_data, eval_data, eval_metric,
                                    validation_metric, epoch_end_callback,
                                    batch_end_callback, eval_end_callback,
                                    eval_batch_end_callback, monitor,
                                    begin_epoch, num_epoch,
                                    checkpoint_prefix, checkpoint_period,
                                    nan_policy, nan_check_period, use_bulk,
                                    bulk_k, _trip_nan_policy, owns_iter,
                                    run=run, resume_nbatch=resume_nbatch,
                                    resume_metric_state=resume_metric_state,
                                    anomaly_policy=anomaly_policy,
                                    anomaly_detector=anomaly_detector,
                                    anomaly_consec=anomaly_consec,
                                    trip_anomaly=_trip_anomaly,
                                    audit_every=audit_every_n_batches,
                                    audit_policy=audit_policy)
                            break
                        except _ELASTIC_RESYNC as e:
                            if elastic_run is None:
                                raise
                            # membership moved: quiesce is NOW (we are at
                            # a batch boundary, or the update that raised
                            # StaleEpoch never landed) — run the reshard
                            # cycle and re-enter the loop in-process
                            self.logger.info(
                                "elastic: quiescing for reshard (%s)", e)
                            begin_epoch, resume_nbatch, \
                                resume_metric_state = elastic_run.sync(
                                    (begin_epoch, resume_nbatch,
                                     resume_metric_state))
                except Exception as e:
                    # crash flight record: preemption, NaN trips and
                    # watchdog hangs dumped at their own sites already
                    # (with richer context); anything else dying out of
                    # fit gets the generic crash dump before the
                    # exception escapes
                    from ..checkpoint import TrainingPreempted

                    if not isinstance(e, (TrainingPreempted,
                                          _sentinel.TrainingWedged)):
                        _perfdebug.flight_dump(
                            "crash",
                            error="%s: %s" % (type(e).__name__, e))
                    if elastic_run is not None:
                        # ANY exit — preemption, NaN raise, a crashed
                        # callback — leaves the job: announce it so the
                        # survivors reshard at their next batch boundary
                        # instead of stalling a full heartbeat deadline
                        # in a sync round this rank will never join
                        # (best-effort; a severed transport falls back
                        # to heartbeat-death eviction)
                        elastic_run.leave()
                    raise
            if writer is not None:
                # clean-path close surfaces a failed background write as
                # an error instead of silently training un-checkpointed
                writer.close()
            if owns_iter:
                # restore fit's postcondition (train_data left reset)
                # only after the producer threads are joined — the
                # wrapper's own reset would re-arm them, racing for the
                # user's first post-fit batch
                fit_data.close()
                train_data.reset()
        finally:
            self._active_ckpt_writer = None
            if watchdog is not None:
                # the monitor thread must never outlive its fit (a
                # stale watchdog would inject into an innocent caller)
                watchdog.stop()
            if writer is not None:
                try:
                    writer.close()
                except Exception as e:  # noqa: broad-except — the clean
                    # path above already surfaced writer errors; here we
                    # must not mask the in-flight exception (preemption,
                    # NaN raise) with a checkpoint-write failure
                    self.logger.warning(
                        "async checkpoint writer close: %s", e)
            if owns_iter:
                fit_data.close()

    def _fit_epochs(self, fit_data, eval_data, eval_metric,
                    validation_metric, epoch_end_callback,
                    batch_end_callback, eval_end_callback,
                    eval_batch_end_callback, monitor, begin_epoch,
                    num_epoch, checkpoint_prefix, checkpoint_period,
                    nan_policy, nan_check_period, use_bulk, bulk_k,
                    _trip_nan_policy, owns_iter=False, run=None,
                    resume_nbatch=None, resume_metric_state=None,
                    anomaly_policy=None, anomaly_detector=None,
                    anomaly_consec=None, trip_anomaly=None,
                    audit_every=None, audit_policy="raise"):
        """The epoch/batch loop body of :meth:`fit` (split out so the
        device-prefetch wrapper can be closed deterministically).

        ``run`` is the per-fit :class:`_FitRun` (snapshot cadence +
        preemption drain); ``resume_nbatch``/``resume_metric_state``
        position the FIRST epoch mid-stream for an exact mid-epoch
        resume — the iterator was already rewound by :meth:`fit`."""
        for epoch in range(begin_epoch, num_epoch):
            tic = time.time()
            eval_metric.reset()
            start_nbatch = -1
            if resume_nbatch is not None and epoch == begin_epoch:
                # continue the interrupted epoch: batch numbering picks
                # up after the last completed batch (cadences — NaN
                # window, snapshots, callbacks — stay aligned with an
                # uninterrupted run) and the metric resumes its sums
                start_nbatch = resume_nbatch
                if resume_metric_state is not None:
                    eval_metric.set_state(resume_metric_state)
            if use_bulk:
                nbatch = start_nbatch
                chunk = []
                device_out = isinstance(eval_metric, _metric.DeviceMetric)

                def _flush(chunk, nbatch):
                    # one span per fused chunk — the bulk-mode analogue
                    # of the per-batch span below
                    bsp = _tracing.start_span("fit.batch", loop=True,
                                              epoch=epoch, k=len(chunk))
                    with _telemetry.phase("bulk_step"):
                        # device metrics consume the stacked outputs
                        # without the (K, ...) host transfer
                        outs = self.run_bulk(
                            chunk, return_outputs="device" if device_out
                            else True)
                    for i, b in enumerate(chunk):
                        nbatch += 1
                        _telemetry.inc("fit.batches")
                        eval_metric.update(b.label, [o[i] for o in outs])
                        if batch_end_callback is not None:
                            bp = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                               eval_metric=eval_metric,
                                               locals=locals())
                            for callback in _as_list(batch_end_callback):
                                callback(bp)
                    bsp.end("ok", nbatch=nbatch)
                    return nbatch

                train_iter = iter(fit_data)
                while True:
                    with _telemetry.phase("data"):
                        data_batch = next(train_iter, _FIT_END)
                    if data_batch is _FIT_END:
                        break
                    chunk.append(data_batch)
                    if len(chunk) == bulk_k:
                        nbatch = _flush(chunk, nbatch)
                        chunk = []
                        if run is not None:
                            # bulk mode snapshots/preempts at chunk
                            # boundaries only — params mid-chunk reflect
                            # later batches' updates (the scan carries
                            # them), so a mid-chunk capture could never
                            # resume exactly
                            run.after_batch(self, epoch, nbatch,
                                            fit_data, eval_metric)
                if chunk:
                    nbatch = _flush(chunk, nbatch)
                    if run is not None:
                        run.after_batch(self, epoch, nbatch, fit_data,
                                        eval_metric)
            else:
                train_iter = iter(fit_data)
                nbatch = start_nbatch
                # True while EVERY unread batch since the last flag read
                # was a staged fused step (whose in-graph gate withheld
                # non-finite updates) — a two-phase batch in the window
                # means a poisoned update may have landed, and the trip
                # log must not claim otherwise
                window_all_staged = True
                while True:
                    # the step phases (data wait / forward+backward /
                    # optimizer+kvstore sync / metric dispatch) land in
                    # telemetry's fit.phase_seconds and, when the profiler
                    # runs, as chrome-trace spans.  JAX dispatch is async:
                    # device compute time surfaces in the first BLOCKING
                    # phase — with device metrics and the in-graph NaN
                    # guard that is the explicit `sync` phase (metric
                    # reads, guard-flag reads), no longer `metric`.
                    with _telemetry.phase("data"):
                        data_batch = next(train_iter, _FIT_END)
                    if data_batch is _FIT_END:
                        break
                    nbatch += 1
                    # per-batch trace span, the root the batch's phases
                    # nest under (data wait excluded — it sits before the
                    # batch starts); disabled-mode cost is two no-op
                    # calls, inside the fit overhead pin.  A batch that
                    # raises leaves it to fit's tracing.frame()
                    bsp = _tracing.start_span("fit.batch", loop=True,
                                              epoch=epoch, nbatch=nbatch)
                    if _faults.should_fire("fit.preempt"):
                        # deterministic preemption: a REAL SIGTERM to
                        # this process — the handler sets the drain flag
                        # and the bottom-of-batch check does the rest,
                        # exactly like a pod eviction would
                        self.logger.warning(
                            "fault 'fit.preempt': delivering SIGTERM at "
                            "epoch %d batch %d", epoch, nbatch)
                        os.kill(os.getpid(), _signal.SIGTERM)
                    if monitor is not None:
                        monitor.tic()
                    with _telemetry.phase("forward_backward"):
                        self.forward_backward(data_batch)
                    if _faults.should_fire("fit.batch"):
                        self.logger.warning(
                            "fault 'fit.batch': poisoning gradients with "
                            "NaN at epoch %d batch %d", epoch, nbatch)
                        self._poison_gradients_nan()
                    if _faults.should_fire("fit.wedge"):
                        self.logger.warning(
                            "fault 'fit.wedge': wedging the step at "
                            "epoch %d batch %d (the hang watchdog must "
                            "trip)", epoch, nbatch)
                        _sentinel.wedge_sleep()
                    nan_detected = False
                    nan_action = None
                    anomaly_detected = False
                    anomaly_action = None
                    staged = bool(getattr(self, "_pending_full", False))
                    window_all_staged = window_all_staged and staged
                    check_nan = nan_policy is not None and \
                        (nbatch + 1) % nan_check_period == 0
                    # guard cadence: the two-phase path checks BEFORE the
                    # update (exact skip); a staged fused step runs first
                    # — its in-graph gate already withheld any non-finite
                    # update — and the accumulated flag is read after.
                    # Either read is one scalar (or a device-side
                    # reduction after an out-of-graph gradient mutation),
                    # never per-array host pulls.
                    tripped = check_nan and not staged \
                        and self._batch_has_nonfinite()
                    anomaly_tripped = False
                    anomaly_value = None
                    if not tripped and anomaly_detector is not None:
                        # grad-norm read BEFORE the update so a
                        # skip/rollback trip really withholds the
                        # poisoned step; a staged fused step is
                        # materialized two-phase first (its gradients
                        # must be inspectable — the monitor trade)
                        with _telemetry.phase("sync"):
                            anomaly_value = self._batch_grad_norm()
                        anomaly_tripped = anomaly_detector.observe(
                            anomaly_value)
                        staged = bool(getattr(self, "_pending_full",
                                              False))
                    if not tripped and not anomaly_tripped:
                        with _telemetry.phase("update"):
                            self.update()
                        if check_nan and staged:
                            tripped = self._batch_has_nonfinite()
                    if tripped:
                        nan_detected = True
                        nan_action = nan_policy
                        _trip_nan_policy(epoch, nbatch,
                                         gated=window_all_staged)
                    elif anomaly_tripped:
                        anomaly_detected = True
                        anomaly_action = anomaly_policy
                        trip_anomaly(epoch, nbatch, anomaly_value)
                    else:
                        if anomaly_consec is not None:
                            anomaly_consec[0] = 0  # clean batch: budget
                            # counts CONSECUTIVE trips only
                        with _telemetry.phase("metric"):
                            self.update_metric(eval_metric,
                                               data_batch.label)
                    if check_nan:
                        window_all_staged = True  # flag consumed: new window
                    _telemetry.inc("fit.batches")
                    if audit_every is not None and \
                            (nbatch + 1) % audit_every == 0:
                        audit = getattr(self, "_run_integrity_audit",
                                        None)
                        if audit is not None:
                            with _telemetry.phase("audit"):
                                audit(audit_policy, checkpoint_prefix,
                                      epoch, nbatch)
                    if monitor is not None:
                        monitor.toc_print()
                    with _telemetry.phase("callbacks"):
                        if batch_end_callback is not None:
                            batch_end_param = BatchEndParam(
                                epoch=epoch, nbatch=nbatch,
                                eval_metric=eval_metric, locals=locals(),
                                nan_detected=nan_detected,
                                nan_action=nan_action,
                                anomaly_detected=anomaly_detected,
                                anomaly_action=anomaly_action)
                            for callback in _as_list(batch_end_callback):
                                callback(batch_end_param)
                        if run is not None:
                            # cadence snapshot + pending-preemption
                            # drain; the guard drain mirrors the
                            # epoch-boundary one so a poisoned window
                            # never checkpoints silently
                            run.after_batch(
                                self, epoch, nbatch, fit_data,
                                eval_metric,
                                drain_guard=lambda e=epoch, b=nbatch,
                                g=window_all_staged:
                                self._drain_nan_window(
                                    nan_policy, nan_check_period, e, b,
                                    g, _trip_nan_policy),
                                # a NaN- or anomaly-tripped batch's
                                # update never landed (skipped or rolled
                                # back): it must not enter the elastic
                                # data ledger as trained
                                data_batch=None
                                if (nan_detected or anomaly_detected)
                                else data_batch)
                    bsp.end("retry" if (nan_detected or anomaly_detected)
                            else "ok")
                # epoch-boundary drain: with nan_check_period > 1 the
                # last window may not have been read yet — a NaN epoch
                # must not survive into checkpoint/eval unflagged
                if nan_policy is not None and nbatch >= 0 and \
                        (nbatch + 1) % nan_check_period != 0 and \
                        self._batch_has_nonfinite():
                    _trip_nan_policy(epoch, nbatch,
                                     gated=window_all_staged)
            for name, val in eval_metric.get_name_value():
                self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
            toc = time.time()
            self.logger.info("Epoch[%d] Time cost=%.3f", epoch, (toc - tic))
            _telemetry.inc("fit.epochs")
            _telemetry.set_gauge("fit.epoch_seconds", toc - tic)
            _telemetry.sample_memory()

            arg_params_, aux_params_ = self.get_params()
            self.set_params(arg_params_, aux_params_)
            if checkpoint_prefix is not None and \
                    ((epoch + 1) % checkpoint_period == 0
                     or epoch + 1 == num_epoch) and \
                    (run is None or run.elastic is None
                     or run.elastic.is_leader()):
                # elastic fits share one prefix: the membership leader
                # owns the epoch checkpoints (like the snapshot cadence)
                with _telemetry.phase("checkpoint"):
                    self._save_fit_checkpoint(checkpoint_prefix, epoch + 1,
                                              arg_params_, aux_params_)
            if epoch_end_callback is not None:
                for callback in _as_list(epoch_end_callback):
                    callback(epoch, self.symbol, arg_params_, aux_params_)
                # user epoch-end work (uploads, evals) emits no phases:
                # it is slow, not wedged — tick the watchdog
                _sentinel.note_progress()
            if eval_data:
                res = self.score(eval_data, validation_metric,
                                 score_end_callback=eval_end_callback,
                                 batch_end_callback=eval_batch_end_callback,
                                 epoch=epoch)
                for name, val in res:
                    self.logger.info("Epoch[%d] Validation-%s=%f",
                                     epoch, name, val)
            if epoch + 1 < num_epoch or not owns_iter:
                # an owned prefetch wrapper skips the FINAL reset: it
                # would re-arm the producer thread, which could consume
                # the user's first post-fit batch before close() lands
                fit_data.reset()
            if run is not None and run.guard is not None and \
                    run.guard.requested is not None:
                # a signal that landed during epoch-end processing
                # (checkpoint save, callbacks, the eval pass) must not
                # be swallowed: the epoch is complete, so the drain
                # point is the epoch BOUNDARY — an epoch checkpoint,
                # not a mid-epoch snapshot of the already-reset iterator
                already_saved = checkpoint_prefix is not None and \
                    ((epoch + 1) % checkpoint_period == 0
                     or epoch + 1 == num_epoch)
                run.epoch_end_preempt(self, epoch, already_saved)

    # -- resilience helpers (docs/resilience.md) --------------------------
    def _drain_nan_window(self, nan_policy, nan_check_period, epoch,
                          nbatch, gated, trip):
        """Preemption-time NaN-guard drain: identical semantics to the
        epoch-boundary drain — a partial read window is flushed so a
        poisoned batch never slips into the final checkpoint unflagged."""
        if nan_policy is not None and nbatch >= 0 and \
                (nbatch + 1) % nan_check_period != 0 and \
                self._batch_has_nonfinite():
            trip(epoch, nbatch, gated=gated)

    def _guard_exec(self):
        """The executor whose gradients the NaN guard inspects: this
        module's, or the active bucket's for BucketingModule."""
        ex = getattr(self, "_exec", None)
        if ex is None:
            ex = getattr(getattr(self, "_curr_module", None), "_exec", None)
        return ex

    def _batch_has_nonfinite(self):
        """True when any output (loss) or parameter gradient of the batch
        just computed contains NaN/Inf.  Device-side either way: the
        executor's accumulated in-graph guard flag when available (ONE
        scalar transfer — the reduction already ran inside the step), else
        one jitted logical-or reduction over the live outputs+grads (the
        path after an out-of-graph gradient mutation, and for modules
        without the fused guard).  Either read lands in the telemetry
        ``sync`` phase."""
        ex = self._guard_exec()
        with _telemetry.phase("sync"):
            if ex is not None and getattr(ex, "_nan_acc", None) is not None \
                    and not getattr(ex, "_nan_stale", False):
                return ex.consume_nan_flag()
            if ex is not None:
                # a stale accumulator predates the mutation that made it
                # stale — discard it and reduce over the arrays as-is
                ex._nan_acc = None
                ex._nan_stale = False
            arrays = [o._jx for o in self.get_outputs()
                      if hasattr(o, "_jx")]
            if ex is not None:
                arrays += [g._jx for g in ex.grad_dict.values()
                           if g is not None]
            from ..executor import any_nonfinite

            try:
                return any_nonfinite(arrays)
            except ValueError:
                # mixed-device arrays (group2ctx placement) cannot share
                # one jit — fall back to per-array host checks
                for a in arrays:
                    v = np.asarray(a)  # host-sync: ok — group2ctx fallback
                    if v.dtype.kind == "f" and not np.isfinite(v).all():
                        return True
                return False

    def _batch_grad_norm(self):
        """Global L2 norm of the batch's parameter gradients as a python
        float — the statistic ``anomaly_policy`` z-scores.  One jitted
        sum-of-squares reduction + a single scalar transfer
        (``executor.global_norm``); a staged fused step is materialized
        first so the gradients exist to inspect."""
        mat = getattr(self, "_materialize_pending", None)
        if mat is not None:
            mat()
        ex = self._guard_exec()
        if ex is None:
            return 0.0
        from ..executor import global_norm

        return global_norm([g._jx for g in ex.grad_dict.values()
                            if g is not None])

    def _poison_gradients_nan(self):
        """fault 'fit.batch': overwrite the first parameter gradient with
        NaN — the observable state of a corrupt reduction/overflow."""
        mat = getattr(self, "_materialize_pending", None)
        if mat is not None:
            mat()  # a staged fused step would recompute (unpoison) grads
        ex = self._guard_exec()
        if ex is None:
            raise MXNetError("fault 'fit.batch' armed but this module "
                             "exposes no gradient arrays")
        for g in ex.grad_dict.values():
            if g is not None:
                g[:] = np.nan
                # the in-graph guard flag predates this mutation: force
                # the next check onto the live-array reduction
                ex._nan_stale = True
                return
        raise MXNetError("fault 'fit.batch' armed but no gradients bound")

    def _rollback_to_checkpoint(self, prefix):
        """nan_policy='rollback': restore params from the newest valid
        checkpoint under ``prefix``."""
        from ..model import load_latest_checkpoint

        found = load_latest_checkpoint(prefix, logger=self.logger)
        if found is None:
            raise MXNetError(
                "nan_policy='rollback': no valid checkpoint under prefix "
                "%r to roll back to" % prefix)
        epoch, _sym, arg_params, aux_params = found
        self.set_params(arg_params, aux_params, force_init=True)
        # restore optimizer state too: post-divergence moments (inflated
        # by the huge pre-NaN gradients) applied to rolled-back weights
        # would immediately re-diverge
        states = "%s-%04d.states" % (prefix, epoch)
        if os.path.exists(states) and hasattr(self,
                                              "load_optimizer_states"):
            self.load_optimizer_states(states)
        else:
            self.logger.warning(
                "rollback: no optimizer state snapshot (%s); keeping "
                "current optimizer moments with epoch-%d parameters",
                states, epoch)
        # mid-epoch snapshots NEWER than the rollback point describe the
        # abandoned (diverging) trajectory — left in place, a later
        # resume='auto' would prefer them and resurrect exactly the
        # state this rollback just discarded.  Quiesce the async writer
        # FIRST: an in-flight pre-NaN snapshot committing after the
        # discard would re-poison the manifest
        from ..checkpoint import discard_snapshots_from

        writer = getattr(self, "_active_ckpt_writer", None)
        if writer is not None:
            try:
                writer.drain()
            except Exception as e:  # noqa: broad-except — a failed
                # background write must not abort the rollback itself
                self.logger.warning(
                    "rollback: async snapshot writer error ignored "
                    "while quiescing (%s)", e)
        discard_snapshots_from(prefix, epoch, logger=self.logger)
        self.logger.info("rolled back parameters to checkpoint epoch %d",
                         epoch)
        _telemetry.inc("resilience.rollbacks")
        _telemetry.event("rollback", to_epoch=epoch, prefix=prefix)
        return epoch

    def _save_fit_checkpoint(self, prefix, epoch, arg_params, aux_params):
        """Per-epoch atomic checkpoint from inside fit (params + optimizer
        states when the module supports them + manifest)."""
        _telemetry.inc("resilience.checkpoint.saves")
        if hasattr(self, "save_checkpoint"):
            self.save_checkpoint(
                prefix, epoch,
                save_optimizer_states=self.optimizer_initialized)
        else:
            from ..model import save_checkpoint as _save_ckpt

            _save_ckpt(prefix, epoch, self.symbol, arg_params, aux_params)
        if _compile_cache.recording():
            # the warm-up manifest rides the checkpoint cadence: a
            # restart replays it to pre-build every executable this fit
            # compiled (no-op when the entry set is unchanged)
            _compile_cache.save_manifest_if_changed(
                _compile_cache.manifest_path(prefix))

    # -- properties / abstract --------------------------------------------
    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self):
        raise NotImplementedError()

    @property
    def output_names(self):
        raise NotImplementedError()

    @property
    def data_shapes(self):
        raise NotImplementedError()

    @property
    def label_shapes(self):
        raise NotImplementedError()

    @property
    def output_shapes(self):
        raise NotImplementedError()

    def get_params(self):
        raise NotImplementedError()

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False):
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)

    def save_params(self, fname):
        arg_params, aux_params = self.get_params()
        save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
        save_dict.update({("aux:%s" % k): v for k, v in aux_params.items()})
        from ..ndarray import save

        save(fname, save_dict)

    def load_params(self, fname):
        from ..ndarray import load

        save_dict = load(fname)
        arg_params = {}
        aux_params = {}
        for k, value in save_dict.items():
            arg_type, name = k.split(":", 1)
            if arg_type == "arg":
                arg_params[name] = value
            elif arg_type == "aux":
                aux_params[name] = value
            else:
                raise ValueError("Invalid param file " + fname)
        self.set_params(arg_params, aux_params)

    def install_monitor(self, mon):
        raise NotImplementedError()

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError()

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        raise NotImplementedError()

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError()


def _as_list(obj):
    if isinstance(obj, (list, tuple)):
        return obj
    return [obj]
