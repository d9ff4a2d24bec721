"""Preemption-tolerant checkpointing: async batch-granular snapshots.

TPU pods preempt.  The TensorFlow paper (Abadi et al., 2016, §4.3)
treats checkpoint/restore as *the* fault-tolerance primitive of a
dataflow system, and the property that makes preemption a non-event is
that snapshots are (a) fine-grained — losing at most a few batches —
and (b) cheap enough to take constantly.  This module supplies both for
``Module.fit`` (docs/resilience.md "Preemption & exact resume"):

* **Capture is device-side and async.**  A snapshot starts as
  ``NDArray.copy()`` of every parameter / aux / optimizer-state array —
  one dispatched device-to-device copy each, no host sync on the
  training loop.  The host-owned smalls (iterator cursor, RNG state,
  metric sums, optimizer update counts) are captured synchronously;
  they are dict-sized.
* **Serialization is one background writer thread.**  The writer pulls
  the captured snapshot, performs the device→host transfer *there*, and
  writes through the ``base.atomic_write`` temp+fsync+rename protocol
  with the manifest updated LAST — a crash at any byte leaves the
  previous generation fully loadable.  Back-pressure is strict: at most
  ONE snapshot may be in flight (queued or writing); a cadence tick
  that lands while the writer is busy is *dropped* and counted
  (``resilience.checkpoint.async_dropped``) rather than queued — two
  in-flight snapshots would double the pinned device copies.
* **Payloads are sha256-verified.**  Every generation records the
  digest of its params/states files in the manifest; resume re-hashes
  before loading and falls back to the previous generation on mismatch
  (``resilience.checkpoint.corrupt_skipped``).
* **Retention is generational.**  ``MXNET_CKPT_KEEP_LAST`` (default 3)
  bounds the on-disk snapshot generations; GC removes a generation's
  manifest entry FIRST, then its payload files, so a crash mid-GC can
  orphan a payload (harmless, swept next GC) but never leave a
  manifest entry pointing at removed bytes.

Telemetry family: ``resilience.checkpoint.async_write_seconds`` /
``resilience.checkpoint.queue_wait_seconds`` (histograms — write
duration, and how long a submitted snapshot waited for the writer
thread), ``resilience.checkpoint.async_inflight`` (gauge),
``resilience.checkpoint.async_dropped`` / ``.corrupt_skipped`` /
``.pruned`` (counters) — see docs/observability.md.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import threading
import time

from . import telemetry as _telemetry
from . import tracing as _tracing
from .base import MXNetError, atomic_write, atomic_write_bytes

__all__ = ["TrainingPreempted", "Snapshot", "TrainingState",
           "AsyncSnapshotWriter", "snapshot_path", "write_snapshot",
           "gc_snapshots", "discard_snapshots_from", "load_latest_state",
           "latest_generation_summary", "keep_last_default"]

#: iterator states larger than this (JSON bytes) move to a per-
#: generation sidecar file instead of the manifest — a shuffled
#: ImageIter's full permutation is O(dataset) and must not be rewritten
#: into the manifest (under its lock) on every cadence tick
ITER_STATE_INLINE_BYTES = 16384


class TrainingPreempted(MXNetError):
    """``Module.fit`` was preempted (SIGTERM/SIGINT) and drained
    gracefully: the in-flight batch finished, accumulators were flushed,
    and a final checkpoint was written.  ``checkpoint_path`` names it
    (None when fit ran without ``checkpoint_prefix``); ``epoch`` /
    ``nbatch`` locate the last completed batch."""

    def __init__(self, msg, checkpoint_path=None, epoch=None, nbatch=None,
                 signum=None):
        super().__init__(msg)
        self.checkpoint_path = checkpoint_path
        self.epoch = epoch
        self.nbatch = nbatch
        self.signum = signum


class Snapshot:
    """One captured mid-epoch training state, pre-serialization.

    ``arg_params``/``aux_params`` map name → NDArray *device copies*;
    ``opt_states`` is the updater's ``{index: state}`` tree of device
    copies (or None when the module has no local updater).  The rest are
    small JSON-able host dicts captured synchronously."""

    __slots__ = ("epoch", "nbatch", "arg_params", "aux_params",
                 "opt_states", "opt_counts", "rng_state", "metric_state",
                 "iter_state", "mesh_info")

    def __init__(self, epoch, nbatch, arg_params, aux_params,
                 opt_states=None, opt_counts=None, rng_state=None,
                 metric_state=None, iter_state=None, mesh_info=None):
        self.epoch = int(epoch)
        self.nbatch = int(nbatch)
        self.arg_params = arg_params
        self.aux_params = aux_params
        self.opt_states = opt_states
        self.opt_counts = opt_counts
        self.rng_state = rng_state
        self.metric_state = metric_state
        self.iter_state = iter_state
        #: sharding descriptor from ``Module._snapshot_mesh_info`` (None
        #: = single payload file): ``{"num_shards": W, "axis": ...,
        #: "mesh_axes": [...], "mesh_shape": [...]}`` — the generation
        #: is then written as W per-shard payload files stitched by the
        #: manifest (docs/how_to/multi_devices.md "Sharded snapshots")
        self.mesh_info = mesh_info


class TrainingState:
    """What resume recovers: the richest verified state under a prefix.

    ``nbatch`` is None for an epoch-boundary checkpoint (resume restarts
    epoch ``epoch`` from batch 0, the pre-existing behavior) and the
    0-based index of the last completed batch for a mid-epoch snapshot
    (resume continues at ``nbatch + 1`` of epoch ``epoch``)."""

    __slots__ = ("epoch", "nbatch", "arg_params", "aux_params",
                 "states_path", "states_bytes", "rng_state",
                 "metric_state", "iter_state", "opt_counts", "path")

    def __init__(self, epoch, nbatch, arg_params, aux_params,
                 states_path=None, states_bytes=None, rng_state=None,
                 metric_state=None, iter_state=None, opt_counts=None,
                 path=None):
        self.epoch = epoch
        self.nbatch = nbatch
        self.arg_params = arg_params
        self.aux_params = aux_params
        self.states_path = states_path
        self.states_bytes = states_bytes
        self.rng_state = rng_state
        self.metric_state = metric_state
        self.iter_state = iter_state
        self.opt_counts = opt_counts
        self.path = path


def keep_last_default():
    """Snapshot generations kept on disk (``MXNET_CKPT_KEEP_LAST``)."""
    return int(os.environ.get("MXNET_CKPT_KEEP_LAST", "3") or 3)


def snapshot_path(prefix, epoch, nbatch, kind="params"):
    """``<prefix>-snap-EEEE-BBBBBB.params`` — distinct from the epoch
    checkpoint namespace (``<prefix>-EEEE.params``), so the epoch scan
    in ``model.list_checkpoints`` never confuses a mid-epoch snapshot
    for a completed epoch."""
    return "%s-snap-%04d-%06d.%s" % (prefix, epoch, nbatch, kind)


def write_snapshot(prefix, snap, logger=logging, keep_last=None):
    """Serialize ``snap`` crash-safely under ``prefix`` (blocking; the
    device→host transfer happens inside).  Payloads first, each atomic;
    the manifest entry (with payload sha256s) is committed LAST; then
    retention GC runs.  Returns the params path."""
    from . import ndarray as nd
    from . import model as _model

    t0 = time.perf_counter()
    csp = _tracing.start_span("checkpoint.write", stack=False,
                              epoch=snap.epoch, nbatch=snap.nbatch)
    mesh_info = getattr(snap, "mesh_info", None)
    if mesh_info:
        params_path, entry = _write_sharded_payloads(prefix, snap,
                                                     mesh_info)
    else:
        params_path = snapshot_path(prefix, snap.epoch, snap.nbatch,
                                    "params")
        save_dict = _snapshot_save_dict(snap)
        # durable=False: snapshot writes stay atomic against PROCESS death
        # (the preemption threat model) but skip the fsync stalls; the
        # fully-durable epoch checkpoint bounds power-loss exposure
        atomic_write(params_path, lambda tmp: nd.save(tmp, save_dict),
                     fault_point="checkpoint.write", durable=False)
        entry = {
            "epoch": snap.epoch, "nbatch": snap.nbatch,
            "params": os.path.basename(params_path),
            "sha256": _model._sha256_file(params_path),
            "states": None, "states_sha256": None,
        }
        if snap.opt_states is not None:
            states_path = snapshot_path(prefix, snap.epoch, snap.nbatch,
                                        "states")
            states_blob = pickle.dumps(snap.opt_states)
            atomic_write_bytes(states_path, states_blob, durable=False)
            entry["states"] = os.path.basename(states_path)
            # hash the in-memory blob — no second read of the file
            entry["states_sha256"] = \
                hashlib.sha256(states_blob).hexdigest()
    entry.update({
        "opt_counts": snap.opt_counts, "rng_state": snap.rng_state,
        "metric_state": snap.metric_state, "iter_state": snap.iter_state,
    })
    if snap.iter_state is not None:
        iter_blob = json.dumps(snap.iter_state).encode()
        if len(iter_blob) > ITER_STATE_INLINE_BYTES:
            # big iterator state (shuffled ImageIter carries the whole
            # epoch permutation) becomes a per-generation sidecar; the
            # manifest keeps only the pointer + digest
            iter_path = snapshot_path(prefix, snap.epoch, snap.nbatch,
                                      "iter.json")
            atomic_write_bytes(iter_path, iter_blob, durable=False)
            entry["iter_state"] = None
            entry["iter_state_file"] = os.path.basename(iter_path)
            entry["iter_state_sha256"] = \
                hashlib.sha256(iter_blob).hexdigest()
    # the commit point: a crash before this line leaves orphan payloads
    # (swept by a later GC), never a manifest entry without its bytes
    _model._manifest_add_snapshot(prefix, entry)
    gc_snapshots(prefix, keep_last=keep_last, logger=logger)
    from . import compile_cache as _compile_cache

    if _compile_cache.recording():
        # warm-up manifest sidecar: a mid-epoch kill before the first
        # EPOCH checkpoint must still leave the resume path something to
        # pre-compile from (no-op once written and unchanged)
        _compile_cache.save_manifest_if_changed(
            _compile_cache.manifest_path(prefix))
    _telemetry.inc("resilience.checkpoint.saves")
    _telemetry.observe("resilience.checkpoint.async_write_seconds",
                       time.perf_counter() - t0)
    _telemetry.event("checkpoint.snapshot", epoch=snap.epoch,
                     nbatch=snap.nbatch, path=params_path)
    csp.end("ok", path=os.path.basename(params_path))
    return params_path


def _snapshot_save_dict(snap):
    """The on-disk key scheme of a snapshot's arrays (``arg:<name>`` /
    ``aux:<name>``) — one definition for both the single-file and the
    per-shard writers, mirrored by the split in the load paths."""
    save_dict = {("arg:%s" % k): v for k, v in snap.arg_params.items()}
    save_dict.update({("aux:%s" % k): v
                      for k, v in snap.aux_params.items()})
    return save_dict


def _write_sharded_payloads(prefix, snap, mesh_info):
    """Sharded snapshot write (``kvstore='mesh'``, world > 1): every
    array/state KEY is assigned to one of ``num_shards`` payload files
    by :func:`mxnet_tpu.elastic.assign_keys` — the same pure ownership
    math the elastic reshard uses — and each shard file is written
    atomically on its own.  The returned manifest ``entry`` carries the
    mesh shape plus each shard's filename + sha256 (the *stitching
    manifest*); committing it LAST means a kill mid-sharded-write
    leaves the previous generation fully loadable.  Resume reads every
    shard named by the manifest and stitches, so a restart onto a
    DIFFERENT mesh shape reassembles the identical state and simply
    re-derives ownership with the new world size for its own writes.
    Returns ``(shard0_path, entry)``."""
    from . import ndarray as nd
    from . import model as _model
    from .elastic import assign_keys

    num_shards = int(mesh_info["num_shards"])
    save_dict = _snapshot_save_dict(snap)
    owner = assign_keys(list(save_dict), list(range(num_shards)), 0)
    state_owner = {}
    if snap.opt_states is not None:
        state_owner = assign_keys(list(snap.opt_states),
                                  list(range(num_shards)), 0)
    shards = []
    first_path = None
    for s in range(num_shards):
        part = {k: v for k, v in save_dict.items() if owner[k] == s}
        path = snapshot_path(prefix, snap.epoch, snap.nbatch,
                             "shard%d.params" % s)
        if first_path is None:
            first_path = path
        atomic_write(path, lambda tmp, part=part: nd.save(tmp, part),
                     fault_point="checkpoint.write", durable=False)
        ent = {"params": os.path.basename(path),
               "sha256": _model._sha256_file(path),
               "states": None, "states_sha256": None}
        if snap.opt_states is not None:
            spart = {i: st for i, st in snap.opt_states.items()
                     if state_owner[i] == s}
            blob = pickle.dumps(spart)
            spath = snapshot_path(prefix, snap.epoch, snap.nbatch,
                                  "shard%d.states" % s)
            atomic_write_bytes(spath, blob, durable=False)
            ent["states"] = os.path.basename(spath)
            ent["states_sha256"] = hashlib.sha256(blob).hexdigest()
        shards.append(ent)
    entry = {
        "epoch": snap.epoch, "nbatch": snap.nbatch,
        "params": None, "sha256": None,
        "states": None, "states_sha256": None,
        "sharded": {"num_shards": num_shards,
                    "axis": mesh_info.get("axis"),
                    "mesh_axes": mesh_info.get("mesh_axes"),
                    "mesh_shape": mesh_info.get("mesh_shape"),
                    "shards": shards},
    }
    return first_path, entry


def _entry_payload_names(entry):
    """Every on-disk payload filename one manifest snapshot entry names
    (single-file generations AND per-shard files of a sharded one) —
    the unit the GC / rollback-discard passes unlink."""
    names = [entry.get(k) for k in _PAYLOAD_KEYS if entry.get(k)]
    for ent in (entry.get("sharded") or {}).get("shards", []):
        for k in ("params", "states"):
            if ent.get(k):
                names.append(ent[k])
    return names


def gc_snapshots(prefix, keep_last=None, logger=logging):
    """Prune snapshot generations beyond ``keep_last`` (newest kept).

    Order is manifest-first: the pruned generations' entries are removed
    (atomic manifest rewrite) BEFORE any payload unlink, so a crash
    mid-GC never leaves the manifest pointing at removed payloads — at
    worst an orphan payload file survives until the next GC pass, which
    also sweeps on-disk ``-snap-`` files no longer in the manifest."""
    from . import model as _model

    if keep_last is None:
        keep_last = keep_last_default()
    if keep_last < 1:
        keep_last = 1
    pruned = _model._manifest_prune_snapshots(prefix, keep_last)
    if not pruned:
        # steady state (≤ keep_last generations): nothing to do — no
        # manifest rewrite, no directory scan.  Orphans from a crash
        # mid-GC wait for the next real prune pass
        return 0
    base_dir = os.path.dirname(os.path.abspath(prefix)) or "."
    victims = []
    for entry in pruned:
        for name in _entry_payload_names(entry):
            victims.append(os.path.join(base_dir, name))
    # orphan sweep: -snap- payloads on disk but absent from the manifest
    # (a previous crash between manifest write and unlink)
    live = set()
    m = _model.checkpoint_manifest(prefix)
    for entry in (m or {}).get("snapshots", []):
        live.update(_entry_payload_names(entry))
    snap_marker = "%s-snap-" % os.path.basename(prefix)
    try:
        for name in os.listdir(base_dir):
            if name.startswith(snap_marker) and name not in live \
                    and (name.endswith(".params")
                         or name.endswith(".states")
                         or name.endswith(".iter.json")):
                victims.append(os.path.join(base_dir, name))
    except OSError:
        pass
    return _unlink_victims(victims, prefix, logger)


#: manifest keys naming on-disk payload files of one snapshot generation
_PAYLOAD_KEYS = ("params", "states", "iter_state_file")


def _unlink_victims(victims, prefix, logger):
    removed = 0
    for path in victims:
        try:
            os.unlink(path)
            removed += 1
        except OSError:
            pass
    if removed:
        _telemetry.inc("resilience.checkpoint.pruned", removed)
        logger.debug("checkpoint GC: removed %d pruned snapshot files "
                     "under %r", removed, prefix)
    return removed


def discard_snapshots_from(prefix, epoch, logger=logging):
    """Drop every snapshot generation at or after 0-based loop epoch
    ``epoch`` — i.e. everything newer than the epoch-``epoch`` boundary
    checkpoint.  ``nan_policy='rollback'`` calls this after restoring:
    snapshots from the abandoned trajectory must not win a later
    ``resume='auto'`` recency race and resurrect the very state the
    rollback discarded.  Manifest-first like :func:`gc_snapshots`."""
    from . import model as _model

    m = _model.checkpoint_manifest(prefix)
    snaps = (m or {}).get("snapshots", [])
    doomed = [s for s in snaps if int(s.get("epoch", -1)) >= epoch]
    if not doomed:
        return 0
    keys = {(_model._snap_key(s)) for s in doomed}

    def _drop(man):
        man["snapshots"] = [s for s in man.get("snapshots", [])
                            if _model._snap_key(s) not in keys]

    _model._manifest_mutate(prefix, _drop, durable=False)
    base_dir = os.path.dirname(os.path.abspath(prefix)) or "."
    victims = [os.path.join(base_dir, name)
               for s in doomed for name in _entry_payload_names(s)]
    logger.info("rollback: discarded %d post-rollback snapshot "
                "generation(s) under %r", len(doomed), prefix)
    return _unlink_victims(victims, prefix, logger)


def _verified(path, want_sha, logger, what):
    """True when ``path`` exists and hashes to ``want_sha`` (a recorded
    digest is mandatory for snapshots — they are never trusted blind)."""
    from . import model as _model

    if not os.path.exists(path):
        logger.warning("resume: %s %s is missing; falling back", what,
                       path)
        return False
    got = _model._sha256_file(path)
    if want_sha and got != want_sha:
        logger.warning(
            "resume: %s %s failed sha256 verification (manifest %s..., "
            "file %s...); falling back to the previous generation",
            what, path, (want_sha or "")[:12], got[:12])
        return False
    return True


def _generation_candidates(prefix, manifest):
    """Every resumable generation under ``prefix`` as ``[(key, kind,
    payload)]`` in the ONE recency convention shared by the verifying
    resume scan and the supervisor's summary probe: an epoch checkpoint
    E sits at key ``(E, -1)`` (so any mid-epoch snapshot of epoch E
    sorts newer), snapshots at ``(epoch, nbatch)`` from the manifest
    (malformed entries skipped)."""
    from . import model as _model

    candidates = []
    for entry in manifest.get("snapshots", []):
        try:
            key = (int(entry["epoch"]), int(entry["nbatch"]))
        except (KeyError, TypeError, ValueError):
            continue
        candidates.append((key, "snapshot", entry))
    for epoch in _model.list_checkpoints(prefix):
        candidates.append(((epoch, -1), "epoch", epoch))
    return candidates


def latest_generation_summary(prefix):
    """Newest resumable generation under ``prefix`` from the MANIFEST
    ALONE — ``{"epoch", "nbatch", "kind"}`` (``nbatch`` None for an
    epoch checkpoint) or None.  No payload reads, no sha verification,
    no array loads: this is the cheap "where would resume='auto' land"
    probe the restart supervisor logs before each relaunch
    (tools/supervise.py ``--prefix``); the authoritative, verifying
    scan is :func:`load_latest_state` over the SAME candidate scan
    (:func:`_generation_candidates`), so the two can't disagree about
    recency."""
    from . import model as _model

    m = _model.checkpoint_manifest(prefix) or {}
    candidates = _generation_candidates(prefix, m)
    if not candidates:
        return None
    (epoch, nbatch), kind, _payload = max(candidates,
                                          key=lambda c: c[0])
    return {"epoch": epoch,
            "nbatch": None if nbatch < 0 else nbatch,
            "kind": "checkpoint" if kind == "epoch" else "snapshot"}


def load_latest_state(prefix, logger=logging, want=None):
    """The richest verified training state under ``prefix``: mid-epoch
    snapshots and epoch-boundary checkpoints in ONE recency order
    (epoch checkpoint E ≡ position ``(E, batch -1)``; snapshot ``(e,
    k)`` sorts after it when ``e > E`` or mid-epoch of ``e == E``).
    Every candidate re-verifies its payload sha256 (and, for epoch
    checkpoints, takes a full load-verify pass) before being trusted;
    corrupt generations are skipped with
    ``resilience.checkpoint.corrupt_skipped`` and the next-older one is
    tried.  With ``want=(epoch, nbatch)`` (nbatch None ≡ an epoch
    checkpoint) only that EXACT generation is considered — the elastic
    reshard's followers load precisely the generation the leader
    announced, never whatever their own manifest view surfaces — and a
    verification failure returns None instead of falling back.
    Returns :class:`TrainingState` or None."""
    from . import model as _model
    from . import ndarray as nd

    m = _model.checkpoint_manifest(prefix) or {}
    base_dir = os.path.dirname(os.path.abspath(prefix)) or "."
    candidates = _generation_candidates(prefix, m)
    if want is not None:
        wkey = (int(want[0]), -1 if want[1] is None else int(want[1]))
        candidates = [c for c in candidates if c[0] == wkey]
    candidates.sort(key=lambda c: c[0], reverse=True)
    for _key, kind, payload in candidates:
        if kind == "epoch":
            epoch = payload
            params = "%s-%04d.params" % (prefix, epoch)
            sha = (m.get("payload_sha256") or {}).get(str(epoch))
            if sha and not _verified(params, sha, logger,
                                     "epoch checkpoint"):
                _telemetry.inc("resilience.checkpoint.corrupt_skipped")
                continue
            try:
                _sym, arg, aux = _model.load_checkpoint(prefix, epoch)
            except (MXNetError, OSError, ValueError) as e:
                logger.warning(
                    "checkpoint %s failed load verification (%s); "
                    "falling back to the previous generation", params, e)
                _telemetry.inc("resilience.checkpoint.corrupt_skipped")
                continue
            states = "%s-%04d.states" % (prefix, epoch)
            return TrainingState(
                epoch=epoch, nbatch=None, arg_params=arg, aux_params=aux,
                states_path=states if os.path.exists(states) else None,
                path=params)
        entry = payload
        arg = aux = None
        states_bytes = None
        params = None
        if entry.get("sharded"):
            # stitched generation: every shard file the manifest names
            # must verify + load; any failure skips the whole generation
            loaded = _load_sharded_payloads(base_dir, entry, logger)
            if loaded is None:
                _telemetry.inc("resilience.checkpoint.corrupt_skipped")
                continue
            arg, aux, states_bytes, params = loaded
        else:
            params = os.path.join(base_dir, entry["params"])
            if not _verified(params, entry.get("sha256"), logger,
                             "snapshot payload"):
                _telemetry.inc("resilience.checkpoint.corrupt_skipped")
                continue
            if entry.get("states"):
                states = os.path.join(base_dir, entry["states"])
                if not _verified(states, entry.get("states_sha256"),
                                 logger, "snapshot optimizer states"):
                    _telemetry.inc(
                        "resilience.checkpoint.corrupt_skipped")
                    continue
                with open(states, "rb") as f:
                    states_bytes = f.read()
        iter_state = entry.get("iter_state")
        if entry.get("iter_state_file"):
            # big iterator state lives in a sidecar (see write_snapshot)
            iter_path = os.path.join(base_dir, entry["iter_state_file"])
            if not _verified(iter_path, entry.get("iter_state_sha256"),
                             logger, "snapshot iterator state"):
                _telemetry.inc("resilience.checkpoint.corrupt_skipped")
                continue
            try:
                with open(iter_path, "rb") as f:
                    iter_state = json.loads(f.read())
            except (OSError, ValueError) as e:
                logger.warning("snapshot iterator state %s failed to "
                               "parse (%s); falling back", iter_path, e)
                _telemetry.inc("resilience.checkpoint.corrupt_skipped")
                continue
        if arg is None:
            try:
                save_dict = nd.load(params)
            except (MXNetError, OSError, ValueError) as e:
                logger.warning("snapshot %s failed load verification "
                               "(%s); falling back", params, e)
                _telemetry.inc("resilience.checkpoint.corrupt_skipped")
                continue
            arg, aux = {}, {}
            for k, v in save_dict.items():
                tp, name = k.split(":", 1)
                if tp == "arg":
                    arg[name] = v
                elif tp == "aux":
                    aux[name] = v
        return TrainingState(
            epoch=int(entry["epoch"]), nbatch=int(entry["nbatch"]),
            arg_params=arg, aux_params=aux, states_bytes=states_bytes,
            rng_state=entry.get("rng_state"),
            metric_state=entry.get("metric_state"),
            iter_state=iter_state,
            opt_counts=entry.get("opt_counts"), path=params)
    return None


def _load_sharded_payloads(base_dir, entry, logger):
    """Verify + stitch one sharded snapshot generation: every shard file
    named by the manifest loads (sha256-verified first), the per-shard
    key subsets union back into the full ``arg``/``aux`` dicts and one
    merged optimizer-state tree.  The stitch is shard-count agnostic —
    it reads whatever the manifest recorded, so a resume onto a
    DIFFERENT mesh shape reassembles the identical state (the new run's
    own snapshots then re-derive key ownership for its world size via
    ``elastic.assign_keys``).  Returns ``(arg, aux, states_bytes,
    first_params_path)`` or None when any shard fails verification."""
    from . import ndarray as nd

    info = entry["sharded"]
    arg, aux = {}, {}
    states = {}
    have_states = False
    first_path = None
    for ent in info.get("shards", []):
        path = os.path.join(base_dir, ent["params"])
        if first_path is None:
            first_path = path
        if not _verified(path, ent.get("sha256"), logger,
                         "sharded snapshot payload"):
            return None
        try:
            save_dict = nd.load(path)
        except (MXNetError, OSError, ValueError) as e:
            logger.warning("sharded snapshot %s failed load verification "
                           "(%s); falling back", path, e)
            return None
        for k, v in save_dict.items():
            tp, name = k.split(":", 1)
            if tp == "arg":
                arg[name] = v
            elif tp == "aux":
                aux[name] = v
        if ent.get("states"):
            spath = os.path.join(base_dir, ent["states"])
            if not _verified(spath, ent.get("states_sha256"), logger,
                             "sharded snapshot optimizer states"):
                return None
            try:
                with open(spath, "rb") as f:
                    states.update(pickle.loads(f.read()))
                have_states = True
            except Exception as e:  # noqa: broad-except — a torn/
                # foreign pickle must fall back, never abort resume
                logger.warning("sharded snapshot states %s failed to "
                               "unpickle (%s); falling back", spath, e)
                return None
    states_bytes = pickle.dumps(states) if have_states else None
    return arg, aux, states_bytes, first_path


class AsyncSnapshotWriter:
    """ONE background thread serializing snapshots for one fit call.

    ``submit`` hands over a captured :class:`Snapshot` without blocking;
    when the writer is busy (writing, or one already queued) the new
    snapshot is DROPPED and counted — strict ≤1-in-flight back-pressure,
    because each pending snapshot pins a full set of device-side copies.
    ``close`` drains the queue (unless ``drain=False``) and JOINS the
    thread — fit's ``finally`` guarantees no leaked writer threads
    (pinned in tests/test_preemption.py)."""

    def __init__(self, prefix, keep_last=None, logger=logging,
                 sync=False):
        self.prefix = prefix
        self.keep_last = keep_last
        self.logger = logger
        #: sync=True serializes inline in submit(): what the async
        #: path is compared with, and a debugging aid
        self.sync = sync
        self._cv = threading.Condition()
        self._slot = None
        self._busy = False
        self._closed = False
        self._error = None
        self._thread = None
        self._warned_drop = False
        if not sync:
            self._thread = threading.Thread(
                target=self._run, name="ckpt-writer", daemon=True)
            self._thread.start()

    def submit(self, snap):
        """Queue ``snap``; False (and a counted drop) when busy."""
        if self.sync:
            self._write(snap)
            return True
        with self._cv:
            if self._closed:
                return False
            if self._busy or self._slot is not None:
                _telemetry.inc("resilience.checkpoint.async_dropped")
                # first drop warns (cadence outruns the writer — worth
                # knowing); the rest go to debug so a tight cadence does
                # not flood the log
                log = self.logger.debug if self._warned_drop \
                    else self.logger.warning
                self._warned_drop = True
                log("async checkpoint: writer busy at epoch %d batch %d; "
                    "snapshot dropped (back-pressure keeps <=1 in "
                    "flight)", snap.epoch, snap.nbatch)
                return False
            # submit timestamp rides along so the writer can histogram
            # how long the snapshot waited before serialization started
            # (resilience.checkpoint.queue_wait_seconds): the diagnostic
            # for "is the <2% async-overhead target writer-bound or
            # cadence-bound" without a bench rerun
            self._slot = (snap, time.perf_counter())
            self._cv.notify_all()
        return True

    def _write(self, snap):
        _telemetry.set_gauge("resilience.checkpoint.async_inflight", 1)
        try:
            write_snapshot(self.prefix, snap, logger=self.logger,
                           keep_last=self.keep_last)
        finally:
            _telemetry.set_gauge("resilience.checkpoint.async_inflight", 0)

    def _run(self):
        while True:
            with self._cv:
                while self._slot is None and not self._closed:
                    self._cv.wait()
                item, self._slot = self._slot, None
                if item is None:  # closed with nothing queued
                    return
                self._busy = True
            snap, t_submit = item
            _telemetry.observe("resilience.checkpoint.queue_wait_seconds",
                               time.perf_counter() - t_submit)
            try:
                self._write(snap)
            except BaseException as e:  # noqa: BLE001 — surfaced on drain
                # published under the condition lock: drain() reads and
                # clears it from the fit thread, and an unguarded
                # cross-thread hand-off can deliver a torn/stale error
                # (flagged by graftlint's lock-discipline pass)
                with self._cv:
                    self._error = e
                self.logger.warning("async checkpoint write failed: %s", e)
            finally:
                with self._cv:
                    self._busy = False
                    self._cv.notify_all()

    def drain(self, timeout=None):
        """Block until no snapshot is queued or being written.  Re-raises
        (once) a writer-thread failure so fit surfaces it instead of
        silently training without checkpoints."""
        if not self.sync:
            with self._cv:
                self._cv.wait_for(
                    lambda: self._slot is None and not self._busy,
                    timeout=timeout)
        with self._cv:
            err, self._error = self._error, None
        if err is not None:
            raise err

    def close(self, drain=True):
        """Stop and JOIN the writer (idempotent).  ``drain=True`` writes
        whatever is still queued first."""
        if self.sync:
            return
        if drain:
            try:
                self.drain()
            except Exception:
                if self._thread is not None:
                    with self._cv:
                        self._closed = True
                        self._cv.notify_all()
                    self._thread.join(timeout=30)
                raise
        with self._cv:
            self._closed = True
            if not drain:
                self._slot = None
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None

    @property
    def alive(self):
        return self._thread is not None and self._thread.is_alive()
