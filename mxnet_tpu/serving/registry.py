"""Versioned multi-model registry — atomic load/reload/unload + warm-up.

A servable model is published to a directory with :func:`save_model`:
``symbol.json`` and ``model.params`` first, ``manifest.json`` LAST via
:func:`mxnet_tpu.base.atomic_write` (PR 1's checkpoint-manifest
convention, fault point ``serving.model.write``).  The manifest carries
sha256 checksums of the payload files, so a reader either loads a
complete, consistent publish or detects a torn one — never silently
serves half-written weights.

:class:`ModelRegistry` maps ``name -> ServedModel``.  ``load``/``reload``
builds and WARMS the new version entirely off-registry — per-bucket
warm-up compilation at load time means first requests never eat an XLA
trace — then swaps it in under the registry lock; any failure (bad
checksum, missing params, injected fault) leaves the previous version
serving untouched.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import threading

import numpy as np

from .. import compile_cache as _compile_cache
from .. import predict as _predict
from .. import telemetry as _telemetry
from ..base import MXNetError, atomic_write, atomic_write_bytes
from ..context import current_context
from .batcher import DynamicBatcher

__all__ = ["UnknownModel", "ServedModel", "ModelRegistry", "save_model",
           "MANIFEST", "WARMUP_MANIFEST"]

#: the publish marker: readers only trust a directory carrying one
MANIFEST = "manifest.json"

#: serializes warm-up build recording across concurrently loading
#: models: compile_cache's recording scope and hit/miss counters are
#: process-global, so two interleaved warm-ups would cross-contaminate
#: each other's manifest entries and cold/warm stats.  Warm-up is a
#: rare load-time event; serializing it is the cheap correct trade.
_warmup_record_lock = threading.Lock()

#: compile-once warm-up manifest (docs/how_to/perf.md "Compile once"):
#: records every executable a load compiled (kind / shape signature /
#: HLO fingerprint) so the NEXT load of the same directory pre-builds
#: them all as persistent-cache loads — version-independent, since the
#: compiled program depends on symbol+shapes, not the weights
WARMUP_MANIFEST = "warmup.json"


class UnknownModel(MXNetError):
    """Request for a model name the registry has not loaded (HTTP 404)."""


def _sha256(path):
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def save_model(model_dir, symbol_json, param_blob, input_shape,
               data_name="data", buckets=(1, 8, 32), version=1, name=None):
    """Publish a servable model directory atomically; returns the
    manifest dict.

    ``input_shape`` is the PER-SAMPLE feature shape (no batch dim);
    ``buckets`` declares the batch-size buckets the server will compile.
    Payload files are VERSION-QUALIFIED (``symbol-v2.json``, ...) and
    written first; the checksummed manifest goes last under the
    ``serving.model.write`` fault point.  A publisher dying anywhere
    mid-publish therefore leaves the previous version fully loadable on
    disk — new payloads never clobber old ones, and the old manifest
    still references intact files.  After a successful publish, payload
    files of superseded versions are garbage-collected best-effort.
    """
    os.makedirs(model_dir, exist_ok=True)
    if hasattr(symbol_json, "tojson"):  # a Symbol
        symbol_json = symbol_json.tojson()
    sym_bytes = symbol_json.encode() if isinstance(symbol_json, str) \
        else bytes(symbol_json)
    version = int(version)
    sym_name = "symbol-v%d.json" % version
    par_name = "model-v%d.params" % version
    atomic_write_bytes(os.path.join(model_dir, sym_name), sym_bytes)
    atomic_write_bytes(os.path.join(model_dir, par_name),
                       bytes(param_blob))
    manifest = {
        "name": name or os.path.basename(os.path.abspath(model_dir)),
        "version": version,
        "symbol": sym_name,
        "params": par_name,
        "data_name": data_name,
        "input_shape": [int(d) for d in input_shape],
        "buckets": sorted({int(b) for b in buckets}),
        "sha256": {
            sym_name: _sha256(os.path.join(model_dir, sym_name)),
            par_name: _sha256(os.path.join(model_dir, par_name)),
        },
    }

    def _write(tmp):
        with open(tmp, "w") as f:
            json.dump(manifest, f, indent=1, sort_keys=True)

    atomic_write(os.path.join(model_dir, MANIFEST), _write,
                 fault_point="serving.model.write")
    # the publish is durable; drop superseded payloads (orphans from a
    # crashed publish get collected by the next successful one)
    for fname in os.listdir(model_dir):
        if fname in (sym_name, par_name, MANIFEST):
            continue
        if fname.startswith(("symbol-v", "model-v")) \
                and ".tmp-" not in fname:
            # never touch a racing publisher's atomic_write temp files
            try:
                os.unlink(os.path.join(model_dir, fname))
            except OSError:  # noqa - best-effort GC, publish already durable
                pass
    return manifest


class ServedModel:
    """One loaded, warm model version: a :class:`~mxnet_tpu.predict.
    Predictor` cycled across the declared batch buckets (all shapes held
    by its bounded executor cache) plus the model's
    :class:`~mxnet_tpu.serving.batcher.DynamicBatcher`."""

    def __init__(self, name, symbol_json, param_blob, input_shape,
                 data_name="data", buckets=(1, 8, 32), version=1,
                 ctx=None, batch_timeout_us=2000, max_queue_depth=128,
                 autostart=True, warmup_manifest=None):
        self.name = name
        self.version = int(version)
        self.data_name = data_name
        self.input_shape = tuple(int(d) for d in input_shape)
        self.buckets = tuple(sorted({int(b) for b in buckets}))
        #: compile-once warm-up manifest of a PREVIOUS load (fingerprint
        #: verification) and the entries THIS load's warm-up recorded
        #: (what the registry persists for the next one)
        self._warmup_manifest = warmup_manifest
        self.warmup_entries = []
        #: warm-up compile accounting for ``describe()`` (None when the
        #: persistent compile cache is disabled)
        self.warmup_cold_compiles = None
        self.warmup_cache_loads = None
        self._pred = _predict.Predictor(
            symbol_json, param_blob,
            {data_name: (self.buckets[-1],) + self.input_shape}, ctx=ctx)
        if self._pred._cache_cap < len(self.buckets):
            # 0 (caching disabled) is equally fatal here: every bucket
            # change would retrace — the exact storm buckets exist to stop
            raise MXNetError(
                "MXNET_PRED_CACHE_SIZE=%d holds fewer executors than the "
                "%d declared buckets of model %r: bucket round-robin "
                "would recompile every dispatch"
                % (self._pred._cache_cap, len(self.buckets), name))
        # the predictor is stateful (set_input/forward); one dispatch at
        # a time per model
        self._run_lock = threading.Lock()
        self.batcher = DynamicBatcher(
            self._dispatch, buckets=self.buckets,
            batch_timeout_us=batch_timeout_us,
            max_queue_depth=max_queue_depth, name=name,
            feature_shape=self.input_shape)
        self.warmup()
        if autostart:
            self.batcher.start()

    def warmup(self):
        """Compile every declared bucket now, at load time, so no live
        request ever eats a first-call XLA trace.

        With the compile-once subsystem active (the default,
        :mod:`mxnet_tpu.compile_cache`), the warm-up's compiles are
        persistent-cache loads on any repeat load of the same
        symbol+shapes — ``serving.warmup.cold_compiles`` reports how
        many executables actually paid a backend compile (0 on a warm
        reload); each bucket's build is recorded into
        :attr:`warmup_entries` and the lowered HLO is fingerprinted
        against the previous load's manifest, a mismatch being the
        cache-invalidation signal (the model's program changed)."""
        import time as _time

        with _warmup_record_lock:
            stats0 = _compile_cache.stats() if _compile_cache.enabled() \
                else None
            with _compile_cache.recording_scope() as rec:
                for b in self.buckets:
                    t0 = _time.perf_counter()
                    self._dispatch(np.zeros((b,) + self.input_shape,
                                            np.float32))
                    _telemetry.observe("serving.warmup.seconds",
                                       _time.perf_counter() - t0,
                                       model=self.name, bucket=b)
            self.warmup_entries = rec.entries
            stats1 = _compile_cache.stats() if stats0 is not None else None
        cold = warm = None
        if stats0 is not None:
            cold = stats1["misses"] - stats0["misses"]
            warm = stats1["hits"] - stats0["hits"]
            _telemetry.set_gauge("serving.warmup.cold_compiles", cold,
                                 model=self.name)
            _telemetry.set_gauge("serving.warmup.cache_loads", warm,
                                 model=self.name)
        self.warmup_cold_compiles = cold
        self.warmup_cache_loads = warm
        self._verify_warmup_fingerprints()
        _telemetry.event("serving.model.warm", model=self.name,
                         version=self.version, buckets=len(self.buckets),
                         cold_compiles=cold, cache_loads=warm)

    def _verify_warmup_fingerprints(self):
        """Compare this load's recorded builds against the previous
        load's warm-up manifest: same (kind, shape signature) lowering
        to different HLO means the model's compiled program changed —
        the invalidation signal operators watch on version swaps."""
        man = self._warmup_manifest
        if not man or not self.warmup_entries:
            return
        prev = {(e.get("kind_name"), e.get("shapes")): e.get("fingerprint")
                for e in man.get("entries", [])}
        for e in self.warmup_entries:
            old = prev.get((e.get("kind_name"), e.get("shapes")))
            new = e.get("fingerprint")
            if old and new and old != new:
                _telemetry.inc("compile_cache.manifest.fingerprint_changes")
                _telemetry.event("compile_cache.fingerprint_change",
                                 model=self.name, kind=e.get("kind_name"),
                                 shapes=e.get("shapes"), old=old, new=new)
                logging.warning(
                    "serving: model %r %s@%s compiles to different HLO "
                    "than the previous load (%s -> %s): the program "
                    "changed, warm-up paid a fresh compile", self.name,
                    e.get("kind_name"), e.get("shapes"), old, new)

    def _dispatch(self, rows):
        """One device dispatch: reshape to the row-count's bucket (an
        executor-cache hit after warm-up), forward, copy out."""
        with self._run_lock:
            shape = (int(rows.shape[0]),) + self.input_shape
            if self._pred._input_shapes[self.data_name] != shape:
                self._pred.reshape({self.data_name: shape})
            self._pred.set_input(self.data_name, rows)
            self._pred.forward()
            return self._pred.get_output(0)

    def pending_rows(self):
        """Rows queued or inside a device dispatch — the graceful-drain
        quiescence probe (uniform across servable kinds; pools expose
        the same method)."""
        return self.batcher.pending_rows()

    def describe(self):
        """Structured model card for ``GET /models`` and the per-model
        ``/healthz`` detail."""
        return {"name": self.name, "version": self.version,
                "kind": "predict", "buckets": list(self.buckets),
                "input_shape": list(self.input_shape),
                "data_name": self.data_name,
                "pending_rows": self.batcher.pending_rows(),
                "warmup": {"entries": len(self.warmup_entries),
                           "cold_compiles": self.warmup_cold_compiles,
                           "cache_loads": self.warmup_cache_loads}}

    def predict(self, data, deadline_ms=None,
                timeout=DynamicBatcher.DEFAULT_TIMEOUT):
        """Serve ``data`` through the batcher.  A single sample (ndim ==
        len(input_shape)) is auto-wrapped and unwrapped; a row batch goes
        through as-is."""
        data = np.asarray(data, np.float32)
        if data.ndim == len(self.input_shape):
            return self.batcher.predict(data[None], deadline_ms=deadline_ms,
                                        timeout=timeout)[0]
        return self.batcher.predict(data, deadline_ms=deadline_ms,
                                    timeout=timeout)

    def close(self, drain=True):
        """Permanent: drains (by default), then fails further submits
        fast — a straggler holding this version across a reload gets a
        typed error, not a hang."""
        self.batcher.close(drain=drain)
        self._pred.free()


class ModelRegistry:
    """``name -> ServedModel`` with atomic swap semantics."""

    def __init__(self, ctx=None, batch_timeout_us=2000,
                 max_queue_depth=128):
        # the chip when there is one, like every other default context:
        # left to ``Predictor``'s own default, a registry built without
        # ``ctx`` served from the host CPU beside an idle chip
        self._ctx = ctx if ctx is not None else current_context()
        self._serve_opts = {"batch_timeout_us": batch_timeout_us,
                            "max_queue_depth": max_queue_depth}
        self._models = {}
        self._lock = threading.Lock()
        #: the attached :class:`~mxnet_tpu.serving.controller.
        #: FleetController` (None when the registry runs uncontrolled);
        #: the frontend's /fleet route and healthz block read it
        self.controller = None

    def attach_controller(self, controller):
        """Attach the fleet controller that manages this registry's
        decode pools (the controller's constructor calls this); the
        frontend resolves it through ``registry.controller``."""
        self.controller = controller
        return controller

    def load(self, name, symbol_json, param_blob, input_shape,
             data_name="data", buckets=(1, 8, 32), version=None,
             warmup_manifest=None):
        """Load (or reload) ``name``: build + warm the new
        :class:`ServedModel` off-registry, then swap atomically.  On any
        build failure the previously loaded version keeps serving.

        ``warmup_manifest`` (a :func:`mxnet_tpu.compile_cache.
        load_manifest` dict — :meth:`load_dir` wires it automatically)
        lets the warm-up verify each compiled bucket's HLO fingerprint
        against the previous load; a RELOAD with no manifest given
        verifies against the version it replaces."""
        prev = self.get(name, default=None)
        if version is None:
            version = 1 if prev is None else prev.version + 1
        if warmup_manifest is None and prev is not None \
                and prev.warmup_entries:
            warmup_manifest = {"entries": prev.warmup_entries}
        model = ServedModel(name, symbol_json, param_blob, input_shape,
                            data_name=data_name, buckets=buckets,
                            version=version, ctx=self._ctx,
                            warmup_manifest=warmup_manifest,
                            **self._serve_opts)
        with self._lock:
            prev = self._models.get(name)
            self._models[name] = model
        if prev is not None:
            prev.close()
        _telemetry.inc("serving.model.loads", model=name)
        _telemetry.event("serving.model.load", model=name, version=version)
        logging.info("serving: model %r v%d loaded on %s (buckets %s)",
                     name, model.version, self._ctx, list(model.buckets))
        return model

    reload = load

    def register(self, name, servable, version=None):
        """Pointer-flip swap of an ALREADY-BUILT servable (a
        :class:`~mxnet_tpu.serving.pool.ReplicaPool`, a
        :class:`ServedModel` constructed off-registry, or anything
        exposing ``version``/``close``/``describe``): the caller builds
        and warms the new version outside the registry — replicas,
        engines, compiled programs, everything — then this swaps it in
        under the registry lock and drains the old one.  No request
        ever sees a half-swapped model; stragglers holding the old
        reference get its typed closed error, not a hang — and a decode
        POOL's in-flight generations MIGRATE onto the new servable
        (``close(successor=...)``: each straggler session re-admits by
        re-prefilling its transcript — bit-identical to an
        uninterrupted run when the versions share params, sampling from
        the new weights' logits otherwise) instead of being errored
        out."""
        if version is not None:
            servable.version = int(version)
        # healthz/models key by servable.name: the registration name is
        # authoritative (build the servable with the same name so its
        # telemetry labels agree — the stamp covers the mismatch case)
        servable.name = name
        with self._lock:
            prev = self._models.get(name)
            if version is None:
                # bare engines carry no version of their own: the
                # registry stamps one so every servable answers
                # .version uniformly
                servable.version = prev.version + 1 if prev is not None \
                    else int(getattr(servable, "version", 1))
            self._models[name] = servable
        if prev is not None:
            if hasattr(prev, "replicas") and (
                    hasattr(servable, "adopt")
                    or hasattr(servable, "resume")):
                # old decode pool -> new decode servable: migrate the
                # stragglers instead of draining/erroring them
                prev.close(successor=servable)
            else:
                prev.close()
        _telemetry.inc("serving.model.loads", model=name)
        _telemetry.event("serving.model.load", model=name,
                         version=servable.version)
        controller = self.controller
        if controller is not None:
            # a pointer flip replaced the pool object: the controller
            # must drop the old pool's autoscale/placement state and
            # adopt the successor on its next tick (best-effort — a
            # controller bug must not fail the swap)
            try:
                controller.on_register(name, servable)
            except Exception:  # noqa: broad-except
                logging.warning("serving: fleet controller on_register "
                                "hook failed for %r", name, exc_info=True)
        logging.info("serving: servable %r v%d registered (%s)",
                     name, servable.version,
                     type(servable).__name__)
        return servable

    @staticmethod
    def _read_manifest(model_dir):
        man_path = os.path.join(model_dir, MANIFEST)
        if not os.path.exists(man_path):
            raise MXNetError("no %s in %r: directory was never fully "
                             "published" % (MANIFEST, model_dir))
        with open(man_path) as f:
            return json.load(f)

    @staticmethod
    def _read_payload(model_dir, man):
        """Read + checksum every manifest-listed file ONCE (reloads are
        the fast path; hashing the in-memory bytes avoids a second pass
        over multi-GB params)."""
        blobs = {}
        for fname, digest in man.get("sha256", {}).items():
            path = os.path.join(model_dir, fname)
            try:
                with open(path, "rb") as f:
                    blob = f.read()
            except OSError as e:
                raise MXNetError(
                    "model file %r listed in the manifest is unreadable "
                    "(torn publish / partial copy?): %s" % (path, e))
            got = hashlib.sha256(blob).hexdigest()
            if got != digest:
                raise MXNetError(
                    "model file %r does not match its manifest checksum "
                    "(torn publish?): %s != %s" % (path, got, digest))
            blobs[fname] = blob
        return blobs

    def load_dir(self, model_dir, name=None, version=None):
        """Load/reload from a :func:`save_model` directory, verifying the
        manifest checksums first — a torn publish raises instead of
        swapping in half-written weights."""
        man = self._read_manifest(model_dir)
        for attempt in (0, 1):
            try:
                blobs = self._read_payload(model_dir, man)
                break
            except MXNetError:
                if attempt == 1:
                    raise
                # a concurrent publish may have GC'd the payloads THIS
                # manifest references; if the manifest moved on, retry
                # once against the newer publish — both were consistent
                new_man = self._read_manifest(model_dir)
                if new_man == man:
                    raise
                man = new_man
        symbol_json = blobs[man["symbol"]].decode()
        param_blob = blobs[man["params"]]
        wu_path = os.path.join(model_dir, WARMUP_MANIFEST)
        warmup_manifest = _compile_cache.load_manifest(wu_path)
        model = self.load(name or man["name"], symbol_json, param_blob,
                          man["input_shape"],
                          data_name=man.get("data_name", "data"),
                          buckets=man.get("buckets", (1, 8, 32)),
                          version=man["version"] if version is None
                          else version,
                          warmup_manifest=warmup_manifest)
        if _compile_cache.recording() and model.warmup_entries:
            # persist what THIS load compiled so the next load (version
            # swap, restart) replays it — atomic, never load-fatal
            try:
                _compile_cache.save_manifest(
                    wu_path, entries=model.warmup_entries,
                    model=model.name)
            except OSError as e:
                logging.warning(
                    "serving: could not write warm-up manifest %s: %s",
                    wu_path, e)
        return model

    def unload(self, name, drain=True):
        """Remove ``name`` and stop its batcher (draining by default)."""
        with self._lock:
            model = self._models.pop(name, None)
        if model is None:
            raise UnknownModel("model %r is not loaded" % name)
        model.close(drain=drain)
        _telemetry.event("serving.model.unload", model=name,
                         version=model.version)

    def get(self, name, **kw):
        with self._lock:
            model = self._models.get(name)
            loaded = sorted(self._models) if model is None else None
        if model is None:
            if "default" in kw:
                return kw["default"]
            raise UnknownModel("model %r is not loaded (have %s)"
                               % (name, loaded))
        return model

    def models(self):
        """Loaded models, sorted by name."""
        with self._lock:
            return sorted(self._models.values(), key=lambda m: m.name)

    def close(self):
        """Unload everything (server shutdown)."""
        with self._lock:
            models, self._models = list(self._models.values()), {}
        for m in models:
            m.close()
