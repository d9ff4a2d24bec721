"""Compile-once infrastructure: persistent XLA compile cache + AOT
warm-up manifests.

Every process used to pay the full trace+compile cost from scratch:
serving warm-up, CI, bench and ``resume="auto"`` all re-compiled
executables whose HLO fingerprints :mod:`mxnet_tpu.perfdebug` already
records.  This module treats compiled executables as durable, reusable
artifacts — the whole-program-compilation idiom of AOT-XLA (Julia→TPU)
and TVM's compiled-kernel artifact reuse — in two tiers:

**Tier 1 — the persistent compilation cache.**  On by default: every
XLA compile first consults JAX's on-disk cache and only compiles on a
miss, writing the serialized executable back for the next process.  The
directory is ``JAX_COMPILATION_CACHE_DIR`` when that is set — this
module adopts it and never points JAX anywhere else — and otherwise the
one fixed path :data:`DEFAULT_DIR` inside the checkout (the path is part
of JAX's cache key, so a directory that moves never hits).
``JAX_ENABLE_COMPILATION_CACHE=false`` keeps the cache off.  This module
owns the operational half the raw JAX knob lacks:

* size/GC bounds — ``MXNET_COMPILE_CACHE_MAX_BYTES`` caps the directory,
  :func:`gc` evicts least-recently-used entries (the ``-atime`` sidecar
  files JAX maintains are the recency signal) and keeps the
  ``xla.compile.persistent_cache_bytes``/``_entries`` gauges fresh;
* corruption safety — a corrupt/truncated entry is NEVER fatal: reads go
  through JAX's non-raising path (we pin
  ``jax_raise_persistent_cache_errors=False``), so a torn entry logs a
  warning, recompiles cleanly and self-heals by overwriting the entry.
  :func:`verify` sweeps undecodable entries out of the directory, and
  everything THIS module writes (manifests) goes through
  ``base.atomic_write``.  The ``compile_cache.read`` fault point
  (:mod:`mxnet_tpu.faults`) truncates a real entry mid-read so the
  fallback is deterministically testable;
* telemetry — persistent hits/misses/saved-seconds are counted under
  ``xla.compile.persistent_cache_*``, SPLIT from the in-process jit
  function cache (``xla.compile.fn_cache_hits`` in ``executor.py``):
  "cold" below always means an actual ``backend.compile`` ran
  (= a persistent-cache miss, or the cache is off).

**Tier 2 — AOT warm-up manifests.**  While recording
(:func:`recording`, implied by tier 1), every executor jit build is
noted with its full identity: executor name, kind, abstract call
signature (shapes/dtypes pytree), shape-signature hash and the
normalized HLO fingerprint from :mod:`mxnet_tpu.perfdebug`.
:func:`save_manifest` persists those entries next to the artifact they
describe — ``<model_dir>/warmup.json`` for a served model,
``<checkpoint_prefix>-warmup.json`` for a training run — and replay
(``Executor.precompile`` / ``Module.warm_from_manifest`` /
``serving.ModelRegistry`` load/reload) AOT-lowers-and-compiles every
recorded program BEFORE traffic or training resumes.  With tier 1
populated the replay is pure cache loads: a version swap or preemption
restart performs **zero cold compiles** on the hot path.  Invalidation
is the HLO fingerprint: a replayed program lowering to different HLO
than the manifest recorded logs a ``compile_cache.fingerprint_change``
event (the manifest is then rewritten from the fresh build).

Cost model: recording adds ONE extra trace (an AOT ``lower``) per jit
build to fingerprint the program — never a second XLA compile, never
any steady-state dispatch cost.  Disabled, every hook is one boolean
check.

See docs/how_to/perf.md "Compile once".
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import threading
import time
from collections import deque

import numpy as np

from . import faults as _faults
from . import perfdebug as _perfdebug
from . import telemetry as _telemetry
from . import tracing as _tracing
from .base import MXNetError, atomic_write

__all__ = [
    "DEFAULT_DIR", "enabled", "recording", "enable", "disable",
    "cache_dir", "stats", "phases", "programs",
    "cache_entries", "cache_size_bytes", "gc", "verify", "note_build",
    "instrument", "records", "recording_scope", "reset_records",
    "manifest_path", "save_manifest", "save_manifest_if_changed",
    "load_manifest", "kind_to_json", "kind_from_json",
    "signature_to_json", "signature_from_json", "MANIFEST_VERSION",
]

_log = logging.getLogger("mxnet_tpu.compile_cache")

#: warm-up manifest schema version (bumped on incompatible changes;
#: :func:`load_manifest` rejects unknown versions)
MANIFEST_VERSION = 1

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset: one
#: fixed path inside the checkout (listed in ``.gitignore``)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")

#: suffixes of one persistent-cache entry: JAX writes the compressed
#: serialized executable to ``<key>-cache`` and touches ``<key>-atime``
#: on every read — the recency signal :func:`gc` evicts by
_CACHE_SUFFIX = "-cache"
_ATIME_SUFFIX = "-atime"

_lock = threading.Lock()
_dir = None            # active cache directory (None = tier 1 off)
_max_bytes = 0         # GC bound (0 = unbounded)
_records = []          # tier-2 build records, in build order
_record_seq = 0        # monotonic build stamp (recording_scope cursor)
_saved_manifests = {}  # path -> content hash (save_manifest_if_changed)
_listening = False     # jax.monitoring listeners installed
_tls = threading.local()  # .hit: the thread's last cache verdict
_orig_get = None       # unwrapped compilation_cache.get_executable_and_time

# process-local persistent-cache counters: kept even when telemetry is
# disabled so stats() (and the CI cache-effectiveness check) always work
_hits = 0
_misses = 0
_saved_seconds = 0.0
_program_seconds = 0.0
_trace_seconds = 0.0
_lower_seconds = 0.0
_lowerings = 0
#: ``(phase, fun_name, t0, t1, tid)`` of the newest things JAX said it
#: did on the way to a program, ``time.monotonic()`` seconds and the
#: thread's native id: a ``trace`` (to a jaxpr; the outermost only, see
#: :func:`_on_duration`), a ``lower`` (jaxpr to MLIR module), and a pass
#: through ``compile_or_get_cached``, a ``load`` where the persistent
#: cache had the program and a ``compile`` where it had not.  A reader
#: cuts at a moment of its own (the benchmark: the opening of its window)
_phases = deque(maxlen=4096)
_evictions = 0
_corrupt_dropped = 0

_COUNTERS = (
    "xla.compile.persistent_cache_hits",
    "xla.compile.persistent_cache_misses",
    "xla.compile.persistent_cache_evictions",
    "xla.compile.persistent_cache_corrupt_dropped",
)


# -- enablement -------------------------------------------------------------
def enabled():
    """True when the persistent compile cache (tier 1) is active."""
    return _dir is not None


def recording():
    """True when jit builds are recorded into the warm-up manifest
    registry (tier 2) — implied by :func:`enabled`; the one check the
    executor's build path makes."""
    return _dir is not None


def cache_dir():
    """The active cache directory, or None."""
    return _dir


def _env_int(name, default):
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_float(name, default):
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return float(default)


def enable(directory=None, max_bytes=None):
    """Activate the two-tier compile cache.

    The directory is ``JAX_COMPILATION_CACHE_DIR`` when that variable is
    set (JAX has already adopted it; a different ``directory`` argument
    is an error, never a silent override), else ``directory``, else
    :data:`DEFAULT_DIR`.  ``max_bytes`` defaults to
    ``MXNET_COMPILE_CACHE_MAX_BYTES`` (0 = unbounded).  Configures JAX's
    persistent compilation cache (min-compile-time floor from
    ``MXNET_COMPILE_CACHE_MIN_COMPILE_SECS``, default 0 so every
    program is cached; corrupt-entry reads NON-fatal), installs the
    hit/miss telemetry listeners, sweeps zero-length entries (full
    decode verification with ``MXNET_COMPILE_CACHE_VERIFY=1``) and
    enforces the size bound.  Idempotent; safe to call after compiles
    already happened (JAX's cached "cache unused" verdict is reset)."""
    global _dir, _max_bytes
    import jax
    from jax._src import compilation_cache as _jcc

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if placed:
        if directory and os.path.abspath(directory) \
                != os.path.abspath(placed):
            raise MXNetError(
                "compile_cache.enable(%r): JAX_COMPILATION_CACHE_DIR=%r "
                "already places the cache; unset it to choose another "
                "directory" % (directory, placed))
        # JAX read the variable itself: adopt its spelling untouched
        directory = placed
    else:
        directory = os.path.abspath(directory or DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", directory)
    os.makedirs(directory, exist_ok=True)
    if max_bytes is None:
        max_bytes = _env_int("MXNET_COMPILE_CACHE_MAX_BYTES", 0)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(os.environ.get("MXNET_COMPILE_CACHE_MIN_COMPILE_SECS", "0")
              or 0.0))
    # cache every executable: the tiny ones are exactly what a serving
    # warm-up / resume replays by the dozen
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # the corruption contract: a torn entry warns + recompiles, never
    # raises into the dispatch that wanted the executable
    jax.config.update("jax_raise_persistent_cache_errors", False)
    # compiles that ran before enable() memoized "cache unused" — drop
    # that verdict (and any stale cache object) so this process caches
    _jcc.reset_cache()
    with _lock:
        _dir = directory
        _max_bytes = max(0, int(max_bytes or 0))
    _install_listeners()
    _install_read_fault_shim()
    if _telemetry.enabled():
        _telemetry.declare(*_COUNTERS)
    dropped = verify(
        deep=os.environ.get("MXNET_COMPILE_CACHE_VERIFY", "0")
        not in ("0", "", "false"))
    evicted = gc()
    _telemetry.event("compile_cache.enabled", dir=directory,
                     max_bytes=_max_bytes, corrupt_dropped=dropped,
                     evicted=evicted)
    _log.info("compile_cache: persistent XLA compile cache at %s "
              "(max_bytes=%s, %d entries / %d bytes)", directory,
              _max_bytes or "unbounded", cache_entries(),
              cache_size_bytes())
    return directory


def disable():
    """Deactivate tier 1 + tier 2 recording (entries on disk are kept).
    JAX's directory setting is left alone — only its enable flag flips,
    so a ``JAX_COMPILATION_CACHE_DIR`` placement survives a re-enable."""
    global _dir
    import jax
    from jax._src import compilation_cache as _jcc

    with _lock:
        _dir = None
    jax.config.update("jax_enable_compilation_cache", False)
    _jcc.reset_cache()


def _init_from_env():
    """Package-import hook: arm the cache (``JAX_COMPILATION_CACHE_DIR``
    or :data:`DEFAULT_DIR`) unless ``JAX_ENABLE_COMPILATION_CACHE`` turned
    it off; never raises (a bad cache dir must not break import)."""
    import jax

    _install_listeners()
    if _dir is not None or not jax.config.jax_enable_compilation_cache:
        return
    try:
        enable()
    except Exception as e:  # noqa: broad-except — import-time guard
        _log.warning("compile_cache: could not enable the persistent "
                     "cache: %s", e)


# -- telemetry listeners ----------------------------------------------------
_EVENT_HITS = "/jax/compilation_cache/cache_hits"
_EVENT_MISSES = "/jax/compilation_cache/cache_misses"
_EVENT_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
_EVENT_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
#: brackets ``compile_or_get_cached`` in JAX 0.9.0, hit or miss: one
#: event a program.  A hit's retrieval time is a part of it
_EVENT_PROGRAM = "/jax/core/compile/backend_compile_duration"
#: brackets one trace of a jitted function to a jaxpr (``pjit.py``).  A
#: function traced inside another's trace publishes its own, inside the
#: outer one's interval: a model's step is some 1,500 of these.  A hit in
#: the trace cache publishes one of a few microseconds
_EVENT_TRACE = "/jax/core/compile/jaxpr_trace_duration"
#: brackets one lowering of a jaxpr to an MLIR module that really ran
#: (``pxla.py``): one event a top-level lowering, none for a lowering
#: JAX had cached
_EVENT_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def _on_event(event, **_kw):
    global _hits, _misses
    if _dir is None:
        return
    if event == _EVENT_HITS:
        _tls.hit = True
        with _lock:
            _hits += 1
        _telemetry.inc("xla.compile.persistent_cache_hits")
    elif event == _EVENT_MISSES:
        with _lock:
            _misses += 1
        _telemetry.inc("xla.compile.persistent_cache_misses")


def _absorb_traces(t0, tid):
    """Drop the newest ``trace`` records of thread ``tid`` that started at
    or after ``t0``: the record about to be kept holds them.  They
    arrived first, so they are the newest; the walk stops at the first
    record that is not one of them.  Lock held by the caller."""
    global _trace_seconds
    while _phases:
        last = _phases[-1]
        if last[0] != "trace" or last[4] != tid or last[2] < t0:
            return
        _trace_seconds -= last[3] - last[2]
        _phases.pop()


def _on_duration(event, duration, fun_name=None, **_kw):
    """Every duration JAX publishes ends here.  The three of
    ``dispatch.py`` (trace, lower, ``compile_or_get_cached``) are kept in
    :data:`_phases` with the end stamped on arrival and the duration taken
    off (JAX's own stamps are ``time.time()``), counted with the cache off
    too.  Of traces the outermost is kept: a step's trace holds some 1,500
    traces of the functions it calls and a kernel's lowering some 500 of
    its own, each published before the one that holds it, so a trace or a
    lowering that arrives takes the place of the traces inside it
    (:func:`_absorb_traces`) and its length is the union of them all.
    Listening only: nothing here calls into JAX."""
    global _saved_seconds, _program_seconds, _trace_seconds, \
        _lower_seconds, _lowerings
    if event in (_EVENT_TRACE, _EVENT_LOWER, _EVENT_PROGRAM):
        t1 = time.monotonic()
        duration = float(duration)
        t0, tid = t1 - duration, threading.get_native_id()
        if event == _EVENT_PROGRAM:
            phase = "load" if getattr(_tls, "hit", False) else "compile"
            _tls.hit = False
        else:
            phase = "trace" if event == _EVENT_TRACE else "lower"
        with _lock:
            if event == _EVENT_PROGRAM:
                _program_seconds += duration
            else:
                _absorb_traces(t0, tid)
                if event == _EVENT_TRACE:
                    _trace_seconds += duration
                else:
                    _lower_seconds += duration
                    _lowerings += 1
            _phases.append((phase, fun_name, t0, t1, tid))
        return
    if _dir is None:
        return
    if event == _EVENT_SAVED:
        with _lock:
            _saved_seconds += max(0.0, float(duration))
        _telemetry.observe("xla.compile.persistent_cache_saved_seconds",
                           duration)
    elif event == _EVENT_RETRIEVAL:
        _telemetry.observe("xla.compile.persistent_cache_retrieval_seconds",
                           duration)


def _install_listeners():
    """Register the jax.monitoring listeners exactly once per process
    (jax offers no unregister; the callbacks early-return when this
    module is disabled)."""
    global _listening
    if _listening:
        return
    import jax.monitoring as _mon

    _mon.register_event_listener(_on_event)
    _mon.register_event_duration_secs_listener(_on_duration)
    _listening = True


# -- corrupt-entry fault point ----------------------------------------------
def _truncate_entry(cache_key):
    """Tear the on-disk entry for ``cache_key`` in half — the state a
    host crash mid-cache-write leaves behind."""
    if _dir is None:
        return
    path = os.path.join(_dir, cache_key + _CACHE_SUFFIX)
    try:
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.truncate(max(1, size // 2))
        _log.warning("fault 'compile_cache.read': truncated cache entry "
                     "%s to %d bytes", path, max(1, size // 2))
    except OSError as e:
        _log.warning("fault 'compile_cache.read': could not truncate "
                     "%s: %s", path, e)


def _install_read_fault_shim():
    """Wrap persistent-cache reads twice over:

    * the ``compile_cache.read`` fault point — when armed and firing,
      the REAL on-disk entry is truncated immediately before JAX reads
      it, so tests exercise the genuine corrupt-entry path (decode
      failure → warning → clean recompile), not a simulation of it;
    * self-healing — JAX's ``LRUCache.put`` is a no-op when the entry
      file already exists, so a torn entry would otherwise stay torn
      FOREVER (every future process warns + recompiles).  A failed read
      therefore drops the torn entry here, letting the recompile's
      write-back land a healthy one."""
    global _orig_get
    if _orig_get is not None:
        return
    from jax._src import compilation_cache as _jcc

    _orig_get = _jcc.get_executable_and_time

    def _guarded(cache_key, compile_options, backend, executable_devices):
        if _dir is not None and _faults.should_fire("compile_cache.read"):
            _truncate_entry(cache_key)
        try:
            return _orig_get(cache_key, compile_options, backend,
                             executable_devices)
        except Exception:
            if _dir is not None:
                _drop_entry(cache_key,
                            os.path.join(_dir, cache_key + _CACHE_SUFFIX),
                            "corrupt")
                _log.warning(
                    "compile_cache: dropped torn persistent-cache entry "
                    "%s after a failed read; the recompile will rewrite "
                    "it", cache_key)
            raise  # jax's non-raising read path turns this into a miss

    _jcc.get_executable_and_time = _guarded


# -- size accounting / GC / verification ------------------------------------
def _entry_list():
    """[(key, cache_path, bytes, atime_seconds)] for every on-disk
    entry, oldest-read first."""
    if _dir is None:
        return []
    out = []
    try:
        names = os.listdir(_dir)
    except OSError:
        return []
    for name in names:
        if not name.endswith(_CACHE_SUFFIX):
            continue
        key = name[:-len(_CACHE_SUFFIX)]
        path = os.path.join(_dir, name)
        try:
            size = os.path.getsize(path)
        except OSError:
            continue  # racing eviction
        atime_path = os.path.join(_dir, key + _ATIME_SUFFIX)
        try:
            atime = os.path.getmtime(atime_path)
        except OSError:
            try:
                atime = os.path.getmtime(path)
            except OSError:
                atime = 0.0
        out.append((key, path, size, atime))
    out.sort(key=lambda e: e[3])
    return out


def cache_entries():
    """Number of executables currently on disk."""
    return len(_entry_list())


def cache_size_bytes():
    """Total bytes of cached executables on disk."""
    return sum(e[2] for e in _entry_list())


def _refresh_gauges(entries=None):
    if entries is None:
        entries = _entry_list()
    _telemetry.set_gauge("xla.compile.persistent_cache_bytes",
                         sum(e[2] for e in entries))
    _telemetry.set_gauge("xla.compile.persistent_cache_entries",
                         len(entries))


def _drop_entry(key, path, counter):
    global _evictions, _corrupt_dropped
    for p in (path, os.path.join(_dir, key + _ATIME_SUFFIX)):
        try:
            os.unlink(p)
        except OSError:
            pass
    if counter == "evicted":
        with _lock:
            _evictions += 1
        _telemetry.inc("xla.compile.persistent_cache_evictions")
    else:
        with _lock:
            _corrupt_dropped += 1
        _telemetry.inc("xla.compile.persistent_cache_corrupt_dropped")


def gc(max_bytes=None):
    """Evict least-recently-used entries until the directory is within
    ``max_bytes`` (default: the bound :func:`enable` was given; 0 =
    unbounded).  Returns the number of evicted entries and refreshes the
    size gauges either way."""
    if _dir is None:
        return 0
    bound = _max_bytes if max_bytes is None else max(0, int(max_bytes))
    entries = _entry_list()
    evicted = 0
    if bound > 0:
        total = sum(e[2] for e in entries)
        while entries and total > bound:
            key, path, size, _atime = entries.pop(0)  # oldest read first
            _drop_entry(key, path, "evicted")
            total -= size
            evicted += 1
            _log.info("compile_cache: evicted %s (%d bytes) — cache over "
                      "the %d-byte bound", key, size, bound)
    _refresh_gauges(entries)
    return evicted


def verify(deep=False):
    """Drop undecodable entries: zero-length always; with ``deep=True``
    every entry is decompressed + split (the full integrity check JAX
    would otherwise only perform lazily at read time).  Returns the
    number of dropped entries."""
    if _dir is None:
        return 0
    dropped = 0
    entries = _entry_list()
    for key, path, size, _atime in entries:
        bad = size == 0
        if not bad and deep:
            try:
                from jax._src import compilation_cache as _jcc

                with open(path, "rb") as f:
                    blob = f.read()
                _jcc.extract_executable_and_time(
                    _jcc.decompress_executable(blob))
            except Exception:  # noqa: broad-except — any decode error
                # means the entry can never load; drop it
                bad = True
        if bad:
            _drop_entry(key, path, "corrupt")
            dropped += 1
            _log.warning("compile_cache: dropped corrupt/truncated cache "
                         "entry %s", key)
    if dropped:
        _refresh_gauges()
    return dropped


_size_memo = (None, 0, 0)  # (mutation stamp, entries, bytes)


def _sized():
    """(entries, bytes) of the on-disk cache, rescanned only when a
    mutation counter moved since the last scan — new entries appear
    exactly on misses, disappear on evictions/corrupt drops — so the
    polled consumers (``/healthz``, per-warmup stats deltas) don't pay
    O(entries) stat calls per read."""
    global _size_memo
    with _lock:
        stamp = (_dir, _misses, _evictions, _corrupt_dropped)
        if stamp == _size_memo[0]:
            return _size_memo[1], _size_memo[2]
    entries = _entry_list()
    n, b = len(entries), sum(e[2] for e in entries)
    with _lock:
        _size_memo = (stamp, n, b)
    return n, b


def stats():
    """Operational snapshot: enabled/dir/entries/bytes plus the
    process-local persistent hit/miss/saved/eviction counters (tracked
    independently of telemetry enablement, so the CI effectiveness check
    and ``/healthz`` always see them)."""
    n_entries, n_bytes = _sized()
    with _lock:
        return {
            "enabled": _dir is not None,
            "dir": _dir,
            "entries": n_entries,
            "bytes": n_bytes,
            "max_bytes": _max_bytes,
            "hits": _hits,
            "misses": _misses,
            "compile_time_saved_seconds": round(_saved_seconds, 3),
            "program_seconds": round(_program_seconds, 6),
            "trace_seconds": round(_trace_seconds, 6),
            "lower_seconds": round(_lower_seconds, 6),
            "lowerings": _lowerings,
            "evictions": _evictions,
            "corrupt_dropped": _corrupt_dropped,
            "recorded_builds": len(_records),
        }


def phases():
    """``(phase, fun_name, t0, t1, tid)`` of the newest traces, lowerings
    and passes through XLA's ``compile_or_get_cached`` in this process (at
    most 4096, oldest first; :data:`_phases` says what each is).  Times
    are ``time.monotonic()`` seconds, ``tid`` the thread's native id, as a
    span's.  Of nested traces the outermost is kept, but where two threads
    trace at once some inner ones may stay: a reader takes the union of a
    phase's intervals, never their sum.  ``lower`` records are the
    lowerings that ran; ``trace`` records are not a count of anything (a
    hit in the trace cache leaves one)."""
    with _lock:
        return list(_phases)


def programs():
    """``(monotonic_s, seconds, hit)`` of the newest programs XLA compiled
    or loaded from the persistent cache in this process, oldest first: the
    seconds are those inside JAX's ``compile_or_get_cached``, the moment
    is its end on ``time.monotonic()``.  A view of :func:`phases`."""
    return [(t1, t1 - t0, phase == "load")
            for phase, _name, t0, t1, _tid in phases()
            if phase in ("load", "compile")]


# -- tier 2: build recording ------------------------------------------------
#: executor kind families the replay path can reconstruct; anything else
#: (placement segments, module-level fused updates) is recorded for the
#: report but skipped by ``Executor.precompile``
REPLAYABLE_KINDS = frozenset({
    "predict", "train", "train_guard", "train_fwd", "train_with_grads",
    "train_sgd", "train_sgd_scan", "predict_scan",
})


def kind_to_json(kind):
    """Executor kind (a string, or a nested tuple of strings/numbers/
    bools) → JSON-safe form, exactly invertible by
    :func:`kind_from_json`."""
    if isinstance(kind, str):
        return kind
    if isinstance(kind, tuple):
        return {"t": "tuple", "items": [kind_to_json(k) for k in kind]}
    if kind is None or isinstance(kind, (bool, int, float)):
        return {"t": "py", "v": kind}
    raise MXNetError("unserializable executor kind element %r" % (kind,))


def kind_from_json(obj):
    if isinstance(obj, str):
        return obj
    if isinstance(obj, dict):
        if obj.get("t") == "tuple":
            return tuple(kind_from_json(i) for i in obj["items"])
        if obj.get("t") == "py":
            return obj["v"]
    raise MXNetError("unreadable manifest kind %r" % (obj,))


def _abstractify(tree):
    """Shapes/dtypes/shardings of a call tree: like ``perfdebug``'s
    abstractify, but a leaf committed to one device keeps its
    ``SingleDeviceSharding`` — committed args lower with an
    ``mhlo.sharding`` annotation, so dropping it would fingerprint (and
    persistent-cache-key) a DIFFERENT program than the real dispatch
    compiles."""
    import jax
    from jax.sharding import SingleDeviceSharding

    def leaf(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            sh = getattr(x, "sharding", None)
            if isinstance(sh, SingleDeviceSharding):
                return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map(leaf, tree)


def _dtype_name(dt):
    return np.dtype(dt).name


def _dtype_from_name(name):
    try:
        return np.dtype(name)
    except TypeError:
        import jax.numpy as jnp

        return np.dtype(getattr(jnp, name))


def _sig_to_json(x):
    if x is None or isinstance(x, (bool, int, float, str)):
        return {"t": "py", "v": x}
    if isinstance(x, (list, tuple)):
        return {"t": "tuple" if isinstance(x, tuple) else "list",
                "items": [_sig_to_json(i) for i in x]}
    if isinstance(x, dict):
        return {"t": "dict",
                "items": {k: _sig_to_json(v) for k, v in sorted(x.items())}}
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        from jax.sharding import SingleDeviceSharding

        node = {"t": "a", "s": [int(d) for d in x.shape],
                "d": _dtype_name(x.dtype)}
        if isinstance(getattr(x, "sharding", None), SingleDeviceSharding):
            # replay re-pins onto the REPLAYING executor's device
            node["sh"] = "single"
        return node
    raise MXNetError("unserializable signature leaf %r" % type(x))


def _sig_from_json(obj, device):
    import jax

    t = obj.get("t")
    if t == "py":
        return obj["v"]
    if t == "list":
        return [_sig_from_json(i, device) for i in obj["items"]]
    if t == "tuple":
        return tuple(_sig_from_json(i, device) for i in obj["items"])
    if t == "dict":
        return {k: _sig_from_json(v, device)
                for k, v in obj["items"].items()}
    if t == "a":
        sharding = None
        if obj.get("sh") == "single" and device is not None:
            from jax.sharding import SingleDeviceSharding

            sharding = SingleDeviceSharding(device)
        return jax.ShapeDtypeStruct(tuple(obj["s"]),
                                    _dtype_from_name(obj["d"]),
                                    sharding=sharding)
    raise MXNetError("unreadable manifest signature node %r" % (obj,))


def signature_to_json(args, kwargs):
    """Abstract call signature (shapes/dtypes/shardings pytree of a jit
    call) → JSON-safe form.  List/tuple/dict structure is preserved
    exactly — jit treats them as distinct pytrees, so replay must
    too."""
    return {"args": [_sig_to_json(a) for a in args],
            "kwargs": {k: _sig_to_json(v)
                       for k, v in sorted((kwargs or {}).items())}}


def signature_from_json(sig, device=None):
    """Inverse of :func:`signature_to_json`: ``(args, kwargs)`` of
    ``jax.ShapeDtypeStruct`` leaves, ready for ``fn.lower(*args,
    **kwargs)``.  ``device`` re-pins single-device-committed leaves so
    the replayed lowering carries the same sharding annotations (and
    therefore the same persistent-cache key) as the real dispatch."""
    args = [_sig_from_json(a, device) for a in sig.get("args", [])]
    kwargs = {k: _sig_from_json(v, device)
              for k, v in sig.get("kwargs", {}).items()}
    return args, kwargs


def note_build(exec_name, kind, lower_fn, args, kwargs=None, seconds=None):
    """Record one freshly built executable into the warm-up registry:
    abstractify the call, AOT-lower it once for the normalized HLO
    fingerprint (``MXNET_COMPILE_CACHE_FINGERPRINT=0`` skips the extra
    trace), and store the full replayable identity.  Never raises into
    the build path.  Returns the entry dict or None."""
    if not recording():
        return None
    try:
        # what is traced and lowered inside this span is done for the
        # manifest's fingerprint alone (``setup.relower_s``)
        with _tracing.setup_span("compile_cache.note_build",
                                 exec=exec_name, kind=_kind_name(kind)):
            return _note_build_impl(exec_name, kind, lower_fn, args,
                                    kwargs or {}, seconds)
    except Exception as e:  # noqa: broad-except — recording failure
        # must never break the dispatch that triggered it
        _log.debug("compile_cache: note_build failed for %s/%s: %s",
                   exec_name, kind, e)
        return None


def _kind_name(kind):
    return kind if isinstance(kind, str) else str(kind[0])


def _note_build_impl(exec_name, kind, lower_fn, args, kwargs, seconds):
    sds_args = _abstractify(args)
    sds_kwargs = _abstractify(kwargs)
    fingerprint = None
    if lower_fn is not None and \
            os.environ.get("MXNET_COMPILE_CACHE_FINGERPRINT", "1") \
            not in ("0", "", "false"):
        try:
            lowered = lower_fn(*sds_args, **sds_kwargs)
            fingerprint = _perfdebug.fingerprint_text(lowered.as_text())
        except Exception as e:  # noqa: broad-except — a program that
            # cannot re-lower abstractly still warms the cache; it just
            # loses invalidation detection
            _log.debug("compile_cache: fingerprint of %s/%s failed: %s",
                       exec_name, kind, e)
    kind_name = _kind_name(kind)
    entry = {
        "exec": exec_name,
        "kind": kind_to_json(kind),
        "kind_name": kind_name,
        "shapes": _perfdebug._shape_sig(sds_args, sds_kwargs),
        "fingerprint": fingerprint,
        "compile_seconds": round(seconds, 4) if seconds else None,
        "sig": signature_to_json(sds_args, sds_kwargs),
    }
    global _record_seq
    with _lock:
        # one entry per identity; a rebuild refreshes the entry and its
        # sequence stamp, so a recording_scope() sees identities rebuilt
        # inside it (a model reload re-builds programs the first load
        # already recorded)
        _record_seq += 1
        entry["_seq"] = _record_seq
        for i, old in enumerate(_records):
            if (old["exec"], old["kind"], old["shapes"]) == \
                    (entry["exec"], entry["kind"], entry["shapes"]):
                _records.pop(i)
                break
        _records.append(entry)
    _telemetry.inc("compile_cache.builds_recorded", kind=kind_name)
    return entry


def instrument(fn, name, kind):
    """Wrap jitted ``fn`` so its first call is recorded into the warm-up
    registry (via perfdebug's shared first-call wrapper); returns ``fn``
    unchanged when recording is off."""
    if not recording():
        return fn
    return _perfdebug.first_call_hook(
        fn, lambda f, args, kwargs, dt: note_build(name, kind, f.lower,
                                                   args, kwargs, dt))


def _public(entry):
    return {k: v for k, v in entry.items() if not k.startswith("_")}


def records():
    """Every recorded build this process, in build order (copies)."""
    with _lock:
        return [_public(e) for e in _records]


def reset_records():
    """Clear the tier-2 registry, save memos and the process-local
    persistent-cache counters (tests)."""
    global _hits, _misses, _saved_seconds, _evictions, _corrupt_dropped
    with _lock:
        _records.clear()
        _saved_manifests.clear()
        _hits = _misses = _evictions = _corrupt_dropped = 0
        _saved_seconds = 0.0


class recording_scope:
    """Context manager capturing the builds (and REbuilds — sequence
    stamps, not list positions) recorded inside its scope — how a
    serving warm-up collects exactly ITS model's entries.  Usable
    (empty) when recording is off."""

    def __init__(self):
        self._start = 0
        self.entries = []

    def __enter__(self):
        with _lock:
            self._start = _record_seq
        return self

    def __exit__(self, *exc):
        with _lock:
            self.entries = [_public(e) for e in _records
                            if e["_seq"] > self._start]
        return False


# -- manifests --------------------------------------------------------------
def manifest_path(prefix):
    """Canonical warm-up manifest path for a checkpoint prefix."""
    return "%s-warmup.json" % prefix


def _manifest_payload(entries, model):
    import jax

    return {
        "version": MANIFEST_VERSION,
        "jax": jax.__version__,
        "model": model,
        "ts": int(time.time()),
        "entries": entries,
    }


def save_manifest(path, entries=None, model=None):
    """Persist a warm-up manifest atomically (``base.atomic_write``);
    ``entries`` defaults to every build recorded this process.  Returns
    ``path``."""
    if entries is None:
        entries = records()
    payload = json.dumps(_manifest_payload(entries, model), indent=1,
                         sort_keys=True)

    def _write(tmp):
        with open(tmp, "w") as f:
            f.write(payload)

    atomic_write(path, _write)
    with _lock:
        _saved_manifests[path] = hashlib.sha256(
            json.dumps(entries, sort_keys=True).encode()).hexdigest()
    _telemetry.inc("compile_cache.manifest.saves")
    return path


def save_manifest_if_changed(path, entries=None, model=None):
    """:func:`save_manifest`, skipped when ``entries`` match what this
    process last wrote to ``path`` (the checkpoint cadence calls this
    every epoch/snapshot; the manifest goes static after the first
    batch).  Never raises — a manifest write failure must not break a
    checkpoint.  Returns the path when written, else None."""
    if entries is None:
        entries = records()
    if not entries:
        return None
    digest = hashlib.sha256(
        json.dumps(entries, sort_keys=True).encode()).hexdigest()
    with _lock:
        if _saved_manifests.get(path) == digest:
            return None
    try:
        return save_manifest(path, entries=entries, model=model)
    except Exception as e:  # noqa: broad-except — best-effort sidecar
        _log.warning("compile_cache: could not write warm-up manifest "
                     "%s: %s", path, e)
        return None


def load_manifest(path):
    """Read a warm-up manifest; returns the dict, or None when absent,
    torn or from an unknown schema version (counted + logged — a bad
    manifest degrades to a cold start, never an error)."""
    if not path or not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            man = json.load(f)
        if not isinstance(man, dict):
            raise ValueError("manifest top level is %s, not an object"
                             % type(man).__name__)
        if man.get("version") != MANIFEST_VERSION:
            raise ValueError("manifest version %r (want %d)"
                             % (man.get("version"), MANIFEST_VERSION))
        if not isinstance(man.get("entries"), list):
            raise ValueError("manifest carries no entry list")
        return man
    except (OSError, ValueError) as e:
        _telemetry.inc("compile_cache.manifest.corrupt")
        _log.warning("compile_cache: unreadable warm-up manifest %s "
                     "(%s); warm-up degrades to lazy compilation",
                     path, e)
        return None
