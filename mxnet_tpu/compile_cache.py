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

**The executable store** (:func:`stored_program`), inside tier 1's
directory and alive exactly when it is: the persistent cache's key is made
from a program's lowered text, so a warm start still traced and lowered
every program to find an executable that was already on disk.  For the
programs a caller hands it (the decode engine's step and prefill buckets)
the store keeps, under a key made WITHOUT tracing (the sources' digest, the
versions, the device, the flags, the build's kind, the call's signature and
the statics the program closes over), what is needed to load the cached
executable and call it; a start that finds the entry loads it, recorded as
a ``load`` in :func:`phases`, and neither traces nor lowers.

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

import functools
import hashlib
import inspect
import json
import logging
import os
import pickle
import sys
import threading
import time
from collections import deque

import numpy as np

from . import faults as _faults
from . import perfdebug as _perfdebug
from . import telemetry as _telemetry
from . import tracing as _tracing
from .base import MXNetError, atomic_write, atomic_write_bytes

__all__ = [
    "DEFAULT_DIR", "enabled", "recording", "enable", "disable",
    "cache_dir", "stats", "phases", "programs", "stored_program",
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
#: an entry of the executable store (:func:`stored_program`), beside them:
#: ``mxstore-<key>-exec``, with the same ``-atime`` sidecar
_STORE_SUFFIX = "-exec"

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
#: records :class:`_Phases` keeps of a process's start, and of its newest
_PHASES_ROOM = 4096


class _Phases:
    """The process's first :data:`_PHASES_ROOM` records and its newest
    :data:`_PHASES_ROOM`, and how many it ``dropped`` between them.  What a
    process does first is its set-up, and what it compiles later, however
    much, pushes none of that out (the benchmark's reference check compiles
    a program for every transcript length a window finished, some 17
    records a request: of one ring of 4096 the 243 requests of a
    ``gpt2-large`` window took all of set-up's records, PR 48)."""

    def __init__(self):
        self.first, self.newest = [], deque(maxlen=_PHASES_ROOM)
        self.dropped = 0

    def append(self, record):
        # ``newest`` holds something only while ``first`` is full
        if len(self.first) < _PHASES_ROOM:
            self.first.append(record)
            return
        if len(self.newest) == _PHASES_ROOM:
            if not self.dropped:
                _log.warning(
                    "compile_cache: phases() keeps the first %d records "
                    "and the newest %d; those between are dropped from "
                    "here on (stats()['phases_dropped'])",
                    _PHASES_ROOM, _PHASES_ROOM)
            self.dropped += 1
        self.newest.append(record)

    def last(self):
        held = self.newest or self.first
        return held[-1] if held else None

    def pop(self):
        (self.newest or self.first).pop()

    def all(self):
        return self.first + list(self.newest)

    def restore(self, records):
        """Hold ``records`` (an earlier :meth:`all`) and nothing else."""
        self.__init__()
        for record in records:
            self.append(record)


#: ``(phase, fun_name, t0, t1, tid)`` of the things JAX said it
#: did on the way to a program, ``time.monotonic()`` seconds and the
#: thread's native id: a ``trace`` (to a jaxpr; the outermost only, see
#: :func:`_on_duration`), a ``lower`` (jaxpr to MLIR module), and a pass
#: through ``compile_or_get_cached``, a ``load`` where the persistent
#: cache had the program and a ``compile`` where it had not.  A reader
#: cuts at a moment of its own (the benchmark: the opening of its window)
_phases = _Phases()
_evictions = 0
_corrupt_dropped = 0
_store_hits = 0        # first calls the executable store served
_store_misses = 0      # ... and those it had no whole entry for
_store_writes = 0
_store_refused = {}    # program -> why the store would not keep it
_config_names = None   # jax's options when the cache first came on

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
    global _dir, _max_bytes, _config_names
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
    if _config_names is None:
        # the options jax has now, as a rule at the package's import: a
        # module imported later defines more (a kernel's first trace
        # imports Pallas and its three), and they must not move the
        # executable store's key between a start that traces a program
        # and one that loads it
        _config_names = frozenset(jax.config.values)
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
    while True:
        last = _phases.last()
        if last is None or last[0] != "trace" or last[4] != tid \
                or last[2] < t0:
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
        # the executable store keeps this key beside what it pickles of
        # the program (_StoredProgram.first)
        _tls.cache_key = cache_key
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
    entry, the executable store's among them, oldest-read first."""
    if _dir is None:
        return []
    out = []
    try:
        names = os.listdir(_dir)
    except OSError:
        return []
    for name in names:
        if not name.endswith((_CACHE_SUFFIX, _STORE_SUFFIX)):
            continue
        key = name[:name.rindex("-")]
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
                if path.endswith(_STORE_SUFFIX):
                    bad = _store_decode(blob) is None
                else:
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


_size_memo = (None, 0, 0, 0)  # (mutation stamp, entries, bytes, the store's)


def _sized():
    """(entries, bytes, the executable store's bytes among them) of the
    on-disk cache, rescanned only when a mutation counter moved since the
    last scan — new entries appear exactly on misses and on the store's
    writes, disappear on evictions/corrupt drops — so the polled
    consumers (``/healthz``, per-warmup stats deltas) don't pay
    O(entries) stat calls per read."""
    global _size_memo
    with _lock:
        stamp = (_dir, _misses, _store_writes, _evictions, _corrupt_dropped)
        if stamp == _size_memo[0]:
            return _size_memo[1:]
    entries = _entry_list()
    sized = (len(entries), sum(e[2] for e in entries),
             sum(e[2] for e in entries if e[1].endswith(_STORE_SUFFIX)))
    with _lock:
        _size_memo = (stamp,) + sized
    return sized


def stats():
    """Operational snapshot: enabled/dir/entries/bytes plus the
    process-local persistent hit/miss/saved/eviction counters (tracked
    independently of telemetry enablement, so the CI effectiveness check
    and ``/healthz`` always see them).  ``entries`` and ``bytes`` are the
    directory's, the executable store's entries among them;
    ``store_hits`` and ``store_misses`` count the first calls
    :func:`stored_program` served and those it had no whole entry for,
    ``store_bytes`` is what its entries hold on disk,
    ``store_refused`` names each program it would not keep, with why, and
    ``phases_dropped`` counts the records :func:`phases` no longer holds."""
    n_entries, n_bytes, store_bytes = _sized()
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
            "phases_dropped": _phases.dropped,
            "evictions": _evictions,
            "corrupt_dropped": _corrupt_dropped,
            "recorded_builds": len(_records),
            "store_hits": _store_hits,
            "store_misses": _store_misses,
            "store_bytes": store_bytes,
            "store_refused": dict(_store_refused),
        }


def phases():
    """``(phase, fun_name, t0, t1, tid)`` of the newest traces, lowerings
    and passes through XLA's ``compile_or_get_cached`` in this process
    (the first 4096 and the newest 4096, oldest first; :data:`_phases` says
    what each is, ``stats()["phases_dropped"]`` how many lay between).  Times
    are ``time.monotonic()`` seconds, ``tid`` the thread's native id, as a
    span's.  Of nested traces the outermost is kept, but where two threads
    trace at once some inner ones may stay: a reader takes the union of a
    phase's intervals, never their sum.  ``lower`` records are the
    lowerings that ran; ``trace`` records are not a count of anything (a
    hit in the trace cache leaves one)."""
    with _lock:
        return _phases.all()


def programs():
    """``(monotonic_s, seconds, hit)`` of the newest programs XLA compiled
    or loaded from the persistent cache in this process, oldest first: the
    seconds are those inside JAX's ``compile_or_get_cached``, the moment
    is its end on ``time.monotonic()``.  A view of :func:`phases`."""
    return [(t1, t1 - t0, phase == "load")
            for phase, _name, t0, t1, _tid in phases()
            if phase in ("load", "compile")]


# -- the executable store -----------------------------------------------------
#: what an entry starts with: then the SHA-256 of the rest, then the rest
_STORE_MAGIC = b"MXEXEC01"
_package_memo = None   # digest of the package's sources, read once a process


class _Undigestible(Exception):
    """The store cannot name everything a program depends on."""


def _package_digest():
    """SHA-256 over every ``.py`` under ``mxnet_tpu/``, names and bytes."""
    global _package_memo
    if _package_memo is None:
        root = os.path.dirname(os.path.abspath(__file__))
        h = hashlib.sha256()
        for where, dirs, files in os.walk(root):
            dirs.sort()
            for name in sorted(f for f in files if f.endswith(".py")):
                path = os.path.join(where, name)
                with open(path, "rb") as f:
                    h.update(b"%s\0%s\0" % (
                        os.path.relpath(path, root).encode(), f.read()))
        _package_memo = h.hexdigest()
    return _package_memo


def _named(obj, files):
    """``module.qualname`` of a class or function; the file that defines it
    joins ``files``, its bytes a part of the key."""
    module = sys.modules.get(obj.__module__)
    path = getattr(module, "__file__", None)
    if path:
        files.add(path)
    return "%s.%s" % (obj.__module__, obj.__qualname__)


def _plain(x, files):
    """A static a program closes over (a model with its ``cfg``, a bucket, a
    dtype) as JSON writes it: values, containers, named tuples, dtypes,
    module-level functions and ``functools.partial`` of them by name, and
    an object as its class and ``vars()``.  What it cannot see into (a
    closure, a bound method, an array) raises :class:`_Undigestible`: a
    program whose statics cannot be told apart is not kept."""
    if x is None or isinstance(x, (bool, int, float, str)):
        return x
    if isinstance(x, np.generic):
        return [type(x).__name__, x.item()]
    if isinstance(x, np.dtype):
        return ["dtype", x.name]
    if isinstance(x, type):
        # a scalar type stands for its dtype (np.float32, jnp.bfloat16)
        if issubclass(x, np.generic) \
                or isinstance(getattr(x, "dtype", None), np.dtype):
            return ["dtype", np.dtype(x).name]
        return _named(x, files)
    if isinstance(x, (tuple, list, set, frozenset)):
        items = [_plain(i, files) for i in x]
        if isinstance(x, (set, frozenset)):
            items.sort(key=repr)
        return [_named(type(x), files)] + items
    if isinstance(x, dict):
        return {"dict": sorted(([_plain(k, files), _plain(v, files)]
                                for k, v in x.items()), key=repr)}
    if isinstance(x, functools.partial):
        return ["partial", _plain(x.func, files), _plain(x.args, files),
                _plain(x.keywords, files)]
    if inspect.isfunction(x):
        if x.__closure__ or "<" in x.__qualname__:
            raise _Undigestible("the closure %s" % x.__qualname__)
        return _named(x, files)
    if hasattr(x, "__dict__") and not callable(x) \
            and not hasattr(x, "shape"):
        return [_named(type(x), files), _plain(vars(x), files)]
    raise _Undigestible("a %s" % type(x).__name__)


def _leaf_signature(x, device):
    """What of one argument decides the program: shape, dtype, whether the
    type is weak, and where it lies."""
    import jax
    from jax.sharding import SingleDeviceSharding

    if isinstance(x, jax.Array):
        if x.committed and not (
                isinstance(x.sharding, SingleDeviceSharding)
                and x.sharding.device_set == {device}):
            raise _Undigestible("an argument laid out as %r" % (x.sharding,))
        return [list(x.shape), x.dtype.name, bool(x.aval.weak_type),
                x.sharding.memory_kind if x.committed else None]
    if isinstance(x, (np.ndarray, np.generic)):
        return [list(x.shape), x.dtype.name, False, None]
    if isinstance(x, (bool, int, float, complex)):
        return [type(x).__name__]
    raise _Undigestible("an argument of type %s" % type(x).__name__)


def _store_key(name, kind, statics, device, args, kwargs):
    """The key of one program's entry: everything its lowered text would
    have held, from what can be read without tracing it.  The sources (this
    package's, and the files of the classes and functions among the
    statics), the versions of ``jax``, ``jaxlib``, the backend and Python,
    the device, ``jax.config`` (the options it had when the cache came on)
    and the environment's ``MXNET_*``, ``JAX_*``, ``XLA_FLAGS`` and
    ``LIBTPU_INIT_ARGS``, whether telemetry counts (an
    entry keeps what a trace counted), the build's name and kind, the
    statics, and the call: the arguments' tree, and each leaf's shape,
    dtype and place.  Weights are arguments: their values are no part."""
    import jax
    import jaxlib

    files = set()
    leaves, tree = jax.tree_util.tree_flatten((args, kwargs))
    parts = {
        "name": name, "kind": _plain(kind, files),
        "statics": _plain(statics, files),
        "tree": str(tree),
        "leaves": [_leaf_signature(x, device) for x in leaves],
        "versions": [jax.__version__, jaxlib.__version__,
                     device.client.platform_version, sys.version],
        "device": [device.platform, device.device_kind, device.id],
        "config": sorted((k, v) for k, v in jax.config.values.items()
                         if k in _config_names),
        "env": sorted((k, v) for k, v in os.environ.items()
                      if k.startswith(("MXNET_", "JAX_"))
                      or k in ("XLA_FLAGS", "LIBTPU_INIT_ARGS")),
        "counts": _telemetry.enabled(),
        "package": _package_digest(),
    }
    package = os.path.dirname(os.path.abspath(__file__)) + os.sep
    sources = {}
    for path in sorted(files):
        if not os.path.abspath(path).startswith(package):
            with open(path, "rb") as f:
                sources[path] = hashlib.sha256(f.read()).hexdigest()
    parts["files"] = sources
    return hashlib.sha256(json.dumps(
        parts, sort_keys=True, default=repr).encode()).hexdigest()


def _store_encode(entry):
    body = pickle.dumps(entry, protocol=pickle.HIGHEST_PROTOCOL)
    return _STORE_MAGIC + hashlib.sha256(body).digest() + body


def _store_decode(blob):
    """The entry ``blob`` holds, or None where it is torn, truncated or
    not one: read only when the digest agrees (what is unpickled is what
    :func:`_store_encode` wrote)."""
    head = len(_STORE_MAGIC) + 32
    if len(blob) <= head or not blob.startswith(_STORE_MAGIC) or \
            hashlib.sha256(blob[head:]).digest() \
            != blob[len(_STORE_MAGIC):head]:
        return None
    return pickle.loads(blob[head:])


def _store_pickle(compiled, cache_key):
    """``compiled`` (a ``jax.stages.Compiled``) as bytes, its trees apart,
    with the executable itself left out: where it stood, the persistent
    cache's key for it.  What ``jax.experimental.serialize_executable``
    pickles, but for that: an executable that was itself loaded from the
    cache does not survive being serialized again (XLA:CPU loses the
    functions it calls), and a second copy of its bytes would double the
    directory."""
    import io

    import jax
    from jax._src.lib import xla_client as xc
    from jax.experimental import serialize_executable as _se

    class Pickler(_se._JaxPjrtPickler):
        def persistent_id(self, obj):
            if isinstance(obj, (xc.LoadedExecutable, xc._xla.Executable)):
                return ("cached", cache_key)
            return super().persistent_id(obj)

    unloaded = getattr(compiled._executable, "_unloaded_executable", None)
    if unloaded is None or compiled._params.const_args or (
            getattr(unloaded, "mut", None) and unloaded.mut.in_mut):
        raise ValueError("the compilation does not support serialization")
    args_info, in_tree = jax.tree_util.tree_flatten(compiled.args_info)
    with io.BytesIO() as file:
        Pickler(file).dump((unloaded, args_info, compiled._no_kwargs))
        return file.getvalue(), in_tree, compiled.out_tree


def _store_unpickle(entry, device):
    """The ``jax.stages.Compiled`` an entry holds, its executable read from
    the persistent cache as a warm compile reads it (through the same
    guard: a torn entry there is dropped); ``KeyError`` where the cache
    has it no more."""
    import io

    import jax
    from jax._src import compilation_cache as _jcc
    from jax.experimental import serialize_executable as _se

    class Unpickler(_se._JaxPjrtUnpickler):
        def persistent_load(self, pid):
            if pid[0] != "cached":
                return super().persistent_load(pid)
            loaded, _seconds = _jcc.get_executable_and_time(
                pid[1], None, self.backend, self.execution_devices)
            if loaded is None:
                raise KeyError(pid[1])
            return loaded

    unloaded, args_info, no_kwargs = Unpickler(
        io.BytesIO(entry["program"]), device.client, [device]).load()
    return jax.stages.Compiled(
        unloaded.load(), [], entry["in_tree"].unflatten(args_info),
        entry["out_tree"], no_kwargs=no_kwargs)


class _StoredProgram:
    """One program's place in the executable store: the first call of its
    ``jit`` (:meth:`first`), and the write after a first call that found
    nothing (:meth:`save`)."""

    def __init__(self, name, kind, statics, device):
        self.name, self.kind, self.statics = name, kind, statics
        self.device = device
        #: whether :meth:`first` served the call from the store
        self.hit = False
        self._path = None       # the entry's; None: the program is refused
        self._counted = {}      # what the miss's trace counted
        self._seq = 0           # builds recorded before the first call
        self._cache_key = None  # the persistent cache's, of the executable

    def _refuse(self, why):
        self._path = None
        with _lock:
            _store_refused["%s/%s" % (self.name, self.kind)] = str(why)
        _log.warning("compile_cache: the executable store does not keep "
                     "%s/%s: %s", self.name, self.kind, why)

    def _load(self, fn, args, kwargs):
        """The stored executable for this call, loaded, or None."""
        global _store_hits, _store_misses, _program_seconds

        if _dir is None:
            return None
        try:
            key = _store_key(self.name, self.kind, self.statics, self.device,
                             args, kwargs)
        except (_Undigestible, OSError) as e:  # OSError: a source unread
            self._refuse(e)
            return None
        stem = os.path.join(_dir, "mxstore-" + key)
        self._path = stem + _STORE_SUFFIX
        t0 = time.monotonic()
        loaded = entry = None
        try:
            with open(self._path, "rb") as f:
                blob = f.read()
        except OSError:
            blob = None
        if blob is not None:
            try:
                entry = _store_decode(blob)
                loaded = _store_unpickle(entry, self.device)
            except Exception as e:  # noqa: broad-except — a torn entry is
                # a miss, never an error: the start goes on as without it
                _log.warning("compile_cache: dropped the store's entry %s "
                             "(%s: %s); it is written again", self._path,
                             type(e).__name__, e)
                _drop_entry("mxstore-" + key, self._path, "corrupt")
        t1 = time.monotonic()
        with _lock:
            if loaded is None:
                _store_misses += 1
                self._seq = _record_seq
                return None
            _store_hits += 1
            _program_seconds += t1 - t0
            _phases.append(("load", "jit(%s)" % getattr(
                fn, "__name__", _kind_name(self.kind)), t0, t1,
                threading.get_native_id()))
        with open(stem + _ATIME_SUFFIX, "w"):
            pass  # read now: what gc() evicts by
        _telemetry.replay(entry["counted"])
        for build in entry["builds"]:
            _record(dict(build))
        self.hit = True
        return loaded

    def first(self, fn, args, kwargs):
        """The program's first call: ``(what it returns, what every later
        call goes through)``.  Where the store holds the program for this
        call its executable is loaded (a ``load`` in :func:`phases`), what
        its trace had counted is counted again (``telemetry.replay``) and
        the builds it had recorded are recorded again, and ``fn`` is
        neither traced nor lowered; handed arguments of another shape
        later, the executable raises ``TypeError``.  Where it holds none,
        ``fn`` is called as it would have been."""
        loaded = self._load(fn, args, kwargs)
        if loaded is not None:
            return loaded(*args, **kwargs), loaded
        _tls.cache_key = None
        with _telemetry.tap() as self._counted:
            out = fn(*args, **kwargs)
        # the call's last look into the persistent cache was for its own
        # program: found or compiled and written, it lies under this key
        self._cache_key = _tls.cache_key
        return out, fn

    def save(self, fn, args, kwargs):
        """After a first call that found no entry (and after the manifest's
        :func:`note_build`): write the program ``fn`` has just been given,
        with what its trace counted and the builds recorded under the
        program's name since.  ``fn.lower`` of the same call finds the
        trace, the lowering and the executable the call made.  Never
        raises: a program that cannot be written is refused by name."""
        global _store_writes

        if self.hit or self._path is None or _dir is None:
            return
        try:
            if self._cache_key is None:
                raise ValueError("its first call asked the persistent "
                                 "cache for no executable")
            program, in_tree, out_tree = _store_pickle(
                fn.lower(*_abstractify(args),
                         **_abstractify(kwargs)).compile(), self._cache_key)
            with _lock:
                builds = [_public(e) for e in _records
                          if e["_seq"] > self._seq and e["exec"] == self.name
                          and e["kind"] == kind_to_json(self.kind)]
            atomic_write_bytes(self._path, _store_encode({
                "program": program, "in_tree": in_tree, "out_tree": out_tree,
                "counted": self._counted, "builds": builds}), durable=False)
        except Exception as e:  # noqa: broad-except — the start has its
            # executable either way; the next one compiles as this one did
            self._refuse("%s: %s" % (type(e).__name__, e))
            return
        with _lock:
            _store_writes += 1


def stored_program(name, kind, statics, device):
    """The executable store's handle for one jitted program, for
    ``perfdebug.first_call_hook(store=)``, or None while the cache is off.

    Beside the persistent cache's entries, in the same directory and under
    the same :func:`gc` bound, the store keeps what
    ``jax.experimental.serialize_executable`` pickles of a program's
    COMPILED executable (its shardings, layouts, donation and trees), and
    for the executable itself the persistent cache's key, under a key of
    its own made without tracing the function (:func:`_store_key`).  A
    start that finds the entry, and the cache's under the key it names,
    loads the executable as a warm compile would and neither traces nor
    lowers the program; the persistent cache needs the lowered text to
    make its key, and a warm start spent most of its time making that
    text.  A start that finds none does what it did, and writes one
    (:meth:`save`, after the first call).  ``name`` and ``kind`` are the build's, as
    :func:`note_build` takes them; ``statics`` is everything the function
    closes over that decides its program, in any shape :func:`_plain` can
    write (what it cannot, refuses the program: ``stats()``
    ``store_refused``); ``device`` is the one device the program runs on.
    An edit to any source under ``mxnet_tpu/`` moves every key: stale
    entries age out under the size bound like any other."""
    if _dir is None:
        return None
    return _StoredProgram(name, kind, statics, device)


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
    return _record(entry)


def _record(entry):
    """Put one build's entry into the registry (a fresh build's, or one the
    executable store kept with the program it loaded)."""
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
    _telemetry.inc("compile_cache.builds_recorded", kind=entry["kind_name"])
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
    global _hits, _misses, _saved_seconds, _evictions, _corrupt_dropped, \
        _store_hits, _store_misses
    with _lock:
        _records.clear()
        _saved_manifests.clear()
        _hits = _misses = _evictions = _corrupt_dropped = 0
        _store_hits = _store_misses = 0
        _store_refused.clear()
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
