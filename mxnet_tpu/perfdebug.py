"""Performance attribution + crash flight recorder.

A regression used to be unexplainable: the framework could time
*phases* (telemetry ``fit.phase_seconds``) but not attribute cost — a
"resnet-50 inference is slower than it was" reading said nothing about
WHICH compiled executable got slower or bigger.  TVM's premise (Chen et
al., 2018) is that op-level cost profiles are the prerequisite for any
fusion/layout tuning; this module is that layer for the XLA executor:

* **Executable attribution** — on every jit build (executor kinds
  ``predict``/``train``/``train_sgd``/placement segments/the fused
  update), capture XLA ``cost_analysis()`` (flops, bytes accessed) and
  ``memory_analysis()`` (argument/output/temp/generated-code bytes —
  the HBM breakdown) per compiled executable, keyed by executor name +
  kind + input-shape signature.  :func:`report` lists every executable;
  telemetry gains ``perf.executable.*`` gauges.
* **HLO fingerprinting** — each lowered program's text is normalized
  (naming-noise annotations stripped) and hashed; re-building the same
  (name, kind, shapes) key with different HLO records a fingerprint
  *change* (:func:`changes`, ``hlo.fingerprint_change`` telemetry
  event, ``perf.fingerprint_changes`` counter).  "Regression vs best"
  becomes "these 2 of 7 executables changed".
  :func:`save_fingerprints` / :func:`diff_fingerprints` compare across
  runs/commits.
* **Live MFU / HBM gauges** — :func:`note_throughput` (called by
  ``Speedometer`` at its log cadence, no extra syncs) combines the
  latest train-step executable's measured flops with the chip's rated
  peak into the ``perf.mfu_pct`` gauge; captures refresh
  ``perf.hbm_peak_bytes``.  Both flow into ``TelemetryReport`` epoch
  lines and the serving ``/metrics`` exposition automatically.
* **Flight recorder** — a bounded in-memory ring of phase timings
  (hooked into ``telemetry.phase``), fingerprint changes and resilience
  marks, dumped ATOMICALLY (``base.atomic_write``) together with the
  recent telemetry events, phase totals and the attribution table on
  crash, NaN-policy trip, ``TrainingPreempted`` and SIGTERM drain — so
  a post-mortem of a chaos-harness kill carries the perf context that
  otherwise evaporates with the process.

Cost model: attribution is OFF by default (``MXNET_PERF_ATTRIB=1`` or
:func:`enable`); when on, each executable's FIRST call additionally
AOT-lowers + compiles the same program for analysis — roughly doubling
one-time compile cost, never touching steady-state dispatch.  The
flight recorder (``MXNET_FLIGHT_RECORDER=1`` or a
``MXNET_FLIGHT_RECORDER_DIR``) costs one ring append per recorded
phase; disabled, both are an early-returning check.

See docs/observability.md "Performance attribution" / "Flight
recorder".
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import re
import threading
import time
from collections import deque

from . import telemetry as _telemetry
from . import tracing as _tracing
from .base import atomic_write

__all__ = [
    "enabled", "enable", "disable", "capture",
    "instrument", "report", "report_text", "fingerprints", "changes",
    "save_fingerprints", "diff_fingerprints", "reset",
    "device_peak_tflops", "step_flops", "note_throughput",
    "flight_enabled", "flight_record", "flight_dump",
    "PEAK_TFLOPS_BY_KIND",
]

_log = logging.getLogger("mxnet_tpu.perfdebug")

#: bf16 dense peak TFLOP/s by PJRT ``device_kind`` (published chip
#: specs) — the denominator of every MFU figure.  A kind that is not
#: listed is an error on measurement paths (:func:`device_peak_tflops`),
#: never a default.
PEAK_TFLOPS_BY_KIND = {
    "TPU v2": 46.0,
    "TPU v3": 123.0,
    "TPU v4": 275.0,
    "TPU v4 lite": 138.0,
    "TPU v5": 459.0,        # v5p
    "TPU v5 lite": 197.0,   # v5e
    "TPU v5e": 197.0,
    "TPU v6 lite": 918.0,   # v6e / Trillium
    "TPU v6e": 918.0,
}

_lock = threading.Lock()
_entries = {}      # (exec, kind, sig) -> attribution entry dict
_changes = []      # fingerprint-change records, in detection order
_latest_step = None  # newest train-family entry (MFU numerator)

_enabled_flag = None   # tri-state: None = follow env, True/False forced


# -- enablement -------------------------------------------------------------
def enabled():
    """True when executable attribution records (``MXNET_PERF_ATTRIB=1``
    or :func:`enable`); consulted once per jit BUILD, never per
    dispatch."""
    if _enabled_flag is not None:
        return _enabled_flag
    return os.environ.get("MXNET_PERF_ATTRIB", "0") \
        not in ("0", "", "false")


def enable():
    global _enabled_flag
    _enabled_flag = True


def disable():
    global _enabled_flag
    _enabled_flag = False


# -- lowering / analysis helpers --------------------------------------------
def _abstractify(tree):
    """Shapes+dtypes only: safe to build AFTER a donating dispatch (aval
    metadata survives donation) and holds no device buffers."""
    import jax

    def leaf(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map(leaf, tree)


#: annotations stripped before hashing: jax records python-side arg/
#: result names (parameter dict keys) into the StableHLO text — naming
#: noise (auto-generated symbol names differ per build) that would flag
#: identical computations as changed
_HLO_NOISE_RE = re.compile(
    r'\s*\{jax\.(?:result_info|arg_info)[^}]*\}')


def fingerprint_text(hlo_text):
    """Stable 16-hex digest of one lowered program, naming noise
    stripped."""
    normalized = _HLO_NOISE_RE.sub("", hlo_text)
    return hashlib.sha256(normalized.encode()).hexdigest()[:16]


def _shape_sig(args, kwargs):
    """Short stable hash of the call's input avals — the 'shape
    signature' half of an attribution key."""
    import jax

    leaves = jax.tree_util.tree_leaves((args, kwargs))
    parts = []
    for x in leaves:
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            parts.append("%s%s" % (getattr(x.dtype, "name", x.dtype),
                                   tuple(x.shape)))
        else:
            parts.append(repr(x))
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:8]


_MEM_FIELDS = (
    ("argument_bytes", "argument_size_in_bytes"),
    ("output_bytes", "output_size_in_bytes"),
    ("temp_bytes", "temp_size_in_bytes"),
    ("alias_bytes", "alias_size_in_bytes"),
    ("generated_code_bytes", "generated_code_size_in_bytes"),
)


def _analyze_lowered(lowered):
    """(fingerprint, flops, bytes_accessed, hbm_breakdown) of one
    lowered program, compiled for the cost/memory numbers.  The live
    capture hook must never raise into the step: a program that will not
    compile or cost out keeps its fingerprint and loses the numbers."""
    fp = fingerprint_text(lowered.as_text())
    try:
        compiled = lowered.compile()
        cost = compiled.cost_analysis() or {}
        m = compiled.memory_analysis()
        mem = {name: int(getattr(m, attr)) for name, attr in _MEM_FIELDS}
    except Exception as e:  # noqa: broad-except — attribution only
        _log.debug("perfdebug: cost/memory analysis failed: %s", e)
        return fp, None, None, {}
    flops = float(cost["flops"]) if cost.get("flops") else None
    bytes_accessed = float(cost["bytes accessed"]) \
        if cost.get("bytes accessed") else None
    return fp, flops, bytes_accessed, mem


def _hbm_total(mem):
    if not mem:
        return None
    return sum(mem.get(k, 0) for k in ("argument_bytes", "output_bytes",
                                       "temp_bytes",
                                       "generated_code_bytes"))


def _device_peak_bytes():
    """Live allocator high-water mark, when the backend exposes one."""
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        return None
    peak = None
    for d in devices:
        stats_fn = getattr(d, "memory_stats", None)
        if stats_fn is None:
            continue
        try:
            stats = stats_fn() or {}
        except Exception:
            continue
        v = stats.get("peak_bytes_in_use")
        if v is not None:
            peak = max(peak or 0, int(v))
    return peak


def _refresh_hbm_gauge():
    """``perf.hbm_peak_bytes``: the device allocator's high-water mark
    when available (TPU), else the largest captured executable's static
    footprint (args+outputs+temp+code)."""
    peak = _device_peak_bytes()
    if peak is None:
        with _lock:
            totals = [_hbm_total(e["hbm"]) for e in _entries.values()]
        totals = [t for t in totals if t]
        peak = max(totals) if totals else None
    if peak is not None:
        _telemetry.set_gauge("perf.hbm_peak_bytes", peak)
    return peak


# -- capture ----------------------------------------------------------------
def capture(name, kind, lower_fn, args, kwargs=None):
    """Attribute one freshly built executable: AOT-lower + compile the
    program via ``lower_fn`` (abstractified ``args``/``kwargs``),
    record cost/memory/fingerprint under key ``(name, kind, shape
    signature)``, and detect fingerprint changes against any previous
    build of the same key.  Never raises — attribution failure must not
    break execution.  Returns the entry dict or None."""
    if not enabled():
        return None
    try:
        return _capture(name, str(kind), lower_fn, args, kwargs or {})
    except Exception as e:
        _log.debug("perfdebug: capture failed for %s/%s: %s", name, kind, e)
        return None


def _capture(name, kind, lower_fn, args, kwargs):
    t0 = time.perf_counter()
    sds_args = _abstractify(args)
    sds_kwargs = _abstractify(kwargs)
    lowered = lower_fn(*sds_args, **sds_kwargs)
    fp, flops, bytes_accessed, mem = _analyze_lowered(lowered)
    sig = _shape_sig(sds_args, sds_kwargs)
    entry = {
        "exec": name, "kind": kind, "shapes": sig,
        "fingerprint": fp, "flops": flops,
        "bytes_accessed": bytes_accessed, "hbm": mem,
        "hbm_total_bytes": _hbm_total(mem), "builds": 1,
    }
    change = None
    global _latest_step
    with _lock:
        prev = _entries.get((name, kind, sig))
        if prev is not None:
            entry["builds"] = prev["builds"] + 1
            if prev["fingerprint"] != fp:
                change = {"ts": round(time.time(), 6), "exec": name,
                          "kind": kind, "shapes": sig,
                          "old": prev["fingerprint"], "new": fp,
                          "old_flops": prev["flops"],
                          "new_flops": flops}
                _changes.append(change)
        _entries[(name, kind, sig)] = entry
        if kind.startswith("train"):
            _latest_step = entry
    if change is not None:
        _telemetry.inc("perf.fingerprint_changes")
        _telemetry.event("hlo.fingerprint_change", **{
            k: v for k, v in change.items() if k != "ts"})
        flight_record("fingerprint_change", **{
            k: v for k, v in change.items() if k != "ts"})
        _log.warning(
            "perfdebug: executable %s/%s@%s changed HLO fingerprint "
            "%s -> %s (flops %s -> %s)", name, kind, sig, change["old"],
            fp, change["old_flops"], flops)
    if flops is not None:
        _telemetry.set_gauge("perf.executable.flops", flops,
                             exec=name, kind=kind)
    if bytes_accessed is not None:
        _telemetry.set_gauge("perf.executable.bytes_accessed",
                             bytes_accessed, exec=name, kind=kind)
    ht = _hbm_total(mem)
    if ht is not None:
        _telemetry.set_gauge("perf.executable.hbm_bytes", ht,
                             exec=name, kind=kind)
    _refresh_hbm_gauge()
    _telemetry.observe("perf.attrib_seconds", time.perf_counter() - t0)
    return entry


class _FirstCallHook:
    """First-call wrapper of a jitted function: ``hook(fn, args, kwargs,
    seconds)`` runs once after the first call, inside the span ``span()``
    opens; a ``store`` makes that call (``compile_cache.stored_program``)."""

    __slots__ = ("_fn", "_call", "_hook", "_pending", "_span", "_store")

    def __init__(self, fn, hook, span=None, store=None):
        self._fn = self._call = fn
        self._hook, self._span, self._store = hook, span, store
        self._pending = True

    def __call__(self, *args, **kwargs):
        if not self._pending:
            return self._call(*args, **kwargs)
        self._pending, t0 = False, time.perf_counter()
        with self._span() if self._span else _tracing.NULL_SPAN:
            if self._store is None:
                out = self._fn(*args, **kwargs)
            else:
                out, self._call = self._store.first(self._fn, args, kwargs)
            self._hook(self._fn, args, kwargs, time.perf_counter() - t0)
        return out

    def lower(self, *args, **kwargs):
        return self._fn.lower(*args, **kwargs)

    def trace(self, *args, **kwargs):
        return self._fn.trace(*args, **kwargs)


def first_call_hook(fn, hook, span=None, store=None):
    """Wrap jitted ``fn`` so ``hook(fn, args, kwargs, seconds)`` fires
    once after its first call.  Shared by perfdebug attribution and
    compile_cache manifest recording.  ``span``, a function of no
    arguments that opens a span (``tracing.setup_span``), makes that call
    and the hook one span: whose trace, lowering and load it was.
    ``store`` (``compile_cache.stored_program``; None: the ``jit`` as it
    is) makes the first call, ``store.first(fn, args, kwargs)``, and gives
    back what every later call goes through: the executable it loaded
    where it holds one for this call, and then ``fn`` is never traced;
    ``fn`` itself where it holds none.  ``lower`` and ``trace`` stay the
    ``jit``'s either way.  (The wrapper's ``lower`` stands on the line it
    stood on: a kernel's lowered text carries the line numbers of its call
    stack, and ``tools/perf/program_fingerprints.py --tpu`` lowers through
    it.)"""
    return _FirstCallHook(fn, hook, span, store)


def instrument(fn, name, kind):
    """Wrap jitted ``fn`` so its first call is attributed; returns
    ``fn`` unchanged when attribution is disabled."""
    if not enabled():
        return fn
    return _FirstCallHook(
        fn, lambda f, args, kwargs, _dt: capture(name, kind, f.lower,
                                                 args, kwargs))


# -- reads ------------------------------------------------------------------
def _key_str(key):
    return "%s/%s@%s" % key


def report():
    """Every captured executable as a list of dicts (exec, kind, shapes,
    fingerprint, flops, bytes_accessed, hbm breakdown, builds), sorted
    by key — the table a bench delta is pinned against."""
    with _lock:
        items = sorted(_entries.items())
    return [dict(e, hbm=dict(e["hbm"])) for _k, e in items]


def report_text():
    """:func:`report` formatted for humans/logs."""
    rows = report()
    if not rows:
        return "perfdebug: no executables captured " \
            "(MXNET_PERF_ATTRIB=1 to enable)"
    head = ("executable", "kind", "shapes", "fingerprint", "gflops",
            "mb_accessed", "hbm_mb", "builds")
    table = [head]
    for e in rows:
        table.append((
            e["exec"], e["kind"], e["shapes"], e["fingerprint"],
            "%.3f" % (e["flops"] / 1e9) if e["flops"] else "-",
            "%.1f" % (e["bytes_accessed"] / 1e6)
            if e["bytes_accessed"] else "-",
            "%.1f" % (e["hbm_total_bytes"] / 1e6)
            if e["hbm_total_bytes"] else "-",
            str(e["builds"])))
    widths = [max(len(r[i]) for r in table) for i in range(len(head))]
    return "\n".join("  ".join(c.ljust(w) for c, w in zip(r, widths))
                     for r in table)


def fingerprints():
    """``{"exec/kind@shapes": fingerprint}`` for every captured
    executable."""
    with _lock:
        return {_key_str(k): e["fingerprint"]
                for k, e in sorted(_entries.items())}


def changes():
    """Fingerprint changes detected this process, in order."""
    with _lock:
        return [dict(c) for c in _changes]


def _write_text(tmp, payload):
    with open(tmp, "w") as f:
        f.write(payload)


def save_fingerprints(path):
    """Persist :func:`fingerprints` as JSON (atomic) for a cross-run /
    cross-commit :func:`diff_fingerprints`; returns ``path``."""
    payload = json.dumps(fingerprints(), indent=1, sort_keys=True)
    atomic_write(path, lambda tmp: _write_text(tmp, payload))
    return path


def diff_fingerprints(path):
    """Compare the current fingerprints against a
    :func:`save_fingerprints` file: ``{"changed": {key: (old, new)},
    "added": [...], "removed": [...]}`` — the "these 2 of 7 executables
    changed" answer across commits."""
    with open(path) as f:
        old = json.load(f)
    now = fingerprints()
    return {
        "changed": {k: (old[k], v) for k, v in now.items()
                    if k in old and old[k] != v},
        "added": sorted(k for k in now if k not in old),
        "removed": sorted(k for k in old if k not in now),
    }


# -- live MFU ---------------------------------------------------------------
def device_peak_tflops(device):
    """Rated bf16 dense peak of ``device`` from :data:`PEAK_TFLOPS_BY_KIND`.
    An unknown ``device_kind`` raises: a measurement must not divide by a
    peak nobody published for the device it ran on."""
    kind = device.device_kind
    if kind not in PEAK_TFLOPS_BY_KIND:
        raise KeyError(
            "no published bf16 peak for device_kind %r: add it to "
            "perfdebug.PEAK_TFLOPS_BY_KIND with its source (known: %s)"
            % (kind, ", ".join(sorted(PEAK_TFLOPS_BY_KIND))))
    return PEAK_TFLOPS_BY_KIND[kind]


def step_flops():
    """Measured flops of the newest captured train-family executable
    (the whole fused/two-phase training step), or None."""
    with _lock:
        if _latest_step is None:
            return None
        return _latest_step["flops"]


def note_throughput(samples_per_sec, batch_size):
    """Fold a measured training rate into the live ``perf.mfu_pct``
    gauge: (samples/sec x flops/sample) / rated peak.  Called by
    ``Speedometer`` at its log cadence — the rate is already measured,
    so this costs no extra device sync.  Returns the MFU percent, or
    None when step flops or the chip peak are unknown."""
    fl = step_flops()
    if not fl or not batch_size or not samples_per_sec:
        return None
    import jax

    # the live gauge stays silent for a kind without a published peak
    # (the CPU, a new chip); measurement paths raise instead
    peak = PEAK_TFLOPS_BY_KIND.get(jax.local_devices()[0].device_kind)
    if not peak:
        return None
    tflops = samples_per_sec * (fl / float(batch_size)) / 1e12
    mfu = 100.0 * tflops / peak
    _telemetry.set_gauge("perf.mfu_pct", mfu)
    _telemetry.set_gauge("perf.tflops", tflops)
    return mfu


# -- flight recorder --------------------------------------------------------
_flight_lock = threading.Lock()
_flight = deque()
_flight_seq = [0]
_flight_flag = None  # tri-state like _enabled_flag


def _flight_dir():
    return os.environ.get("MXNET_FLIGHT_RECORDER_DIR", "")


_flight_size_cache = (None, 512)  # (raw env value, parsed size)


def _flight_size():
    """Ring capacity, memoized on the raw env string so the per-append
    cost is one dict get + compare, not an int() parse."""
    global _flight_size_cache
    raw = os.environ.get("MXNET_FLIGHT_RECORDER_SIZE", "")
    if raw != _flight_size_cache[0]:
        try:
            size = max(16, int(raw or 512))
        except ValueError:
            size = 512
        _flight_size_cache = (raw, size)
    return _flight_size_cache[1]


def flight_enabled():
    """True when the flight recorder rings/dumps:
    ``MXNET_FLIGHT_RECORDER=1``, a ``MXNET_FLIGHT_RECORDER_DIR``, or
    :func:`enable_flight_recorder`."""
    if _flight_flag is not None:
        return _flight_flag
    if os.environ.get("MXNET_FLIGHT_RECORDER", "") \
            not in ("", "0", "false"):
        return True
    return bool(_flight_dir())


def enable_flight_recorder():
    global _flight_flag
    _flight_flag = True
    # dumps are built from telemetry's event ring + phase timings: an
    # armed recorder over disabled telemetry would record nothing (the
    # env-armed spelling gets the same implication at telemetry import)
    _telemetry.enable()


def disable_flight_recorder():
    global _flight_flag
    _flight_flag = False


def flight_record(kind, **fields):
    """Append one record to the bounded in-memory ring (phase timings
    arrive here automatically through the telemetry phase hook)."""
    if not flight_enabled():
        return
    _flight_append(kind, fields)


def _flight_append(kind, fields):
    rec = {"ts": round(time.time(), 6), "kind": kind}
    rec.update(fields)
    with _flight_lock:
        _flight.append(rec)
        limit = _flight_size()
        while len(_flight) > limit:
            _flight.popleft()


def _telemetry_phase_hook(family, phase, seconds):
    # installed into telemetry.phase at import: each timed phase becomes
    # one ring record, so a dump carries the LAST batches' per-phase
    # durations, not just lifetime histograms.  ONE enablement check
    # here, then straight to the append — this runs a few times per
    # batch on the sync-free fit hot loop
    if flight_enabled():
        _flight_append("phase", {"family": family, "phase": phase,
                                 "seconds": round(seconds, 6)})


_telemetry.add_phase_hook(_telemetry_phase_hook)


def flight_dump(reason, **fields):
    """Dump the flight recorder atomically to
    ``MXNET_FLIGHT_RECORDER_DIR`` (default ``.``): the ring, the recent
    telemetry events, per-family phase totals, the attribution table,
    fingerprints and changes, and the perf gauges.  Called on crash,
    NaN-policy trip, preemption and SIGTERM drain; never raises.
    Returns the dump path, or None when disabled/failed."""
    if not flight_enabled():
        return None
    try:
        return _flight_dump_impl(reason, fields)
    except Exception as e:
        _log.warning("perfdebug: flight-recorder dump failed: %s", e)
        return None


def _flight_dump_impl(reason, fields):
    directory = _flight_dir() or "."
    if directory and not os.path.isdir(directory):
        os.makedirs(directory, exist_ok=True)
    with _flight_lock:
        records = list(_flight)
        _flight_seq[0] += 1
        seq = _flight_seq[0]
    phase_totals = {}
    for family in ("fit", "bulk", "serving", "bench", "io"):
        totals = _telemetry.phase_totals(family)
        if totals:
            phase_totals[family] = {
                ph: {"seconds": s, "count": n}
                for ph, (s, n) in sorted(totals.items())}
    payload = {
        "reason": reason,
        "detail": fields,
        "ts": round(time.time(), 6),
        "pid": os.getpid(),
        "records": records,
        "events": _telemetry.events_recent(100),
        "phase_totals": phase_totals,
        "attribution": report(),
        "fingerprints": fingerprints(),
        "fingerprint_changes": changes(),
        "gauges": {
            "perf.mfu_pct": _telemetry.gauge_value("perf.mfu_pct"),
            "perf.hbm_peak_bytes":
                _telemetry.gauge_value("perf.hbm_peak_bytes"),
        },
    }
    safe_reason = re.sub(r"[^A-Za-z0-9_.-]", "_", str(reason))[:40]
    # set-up's spans are there with tracing off: why this start took as
    # long as it did is asked of a dump too
    spans = _tracing.setup_spans()
    if _tracing.enabled():
        spans += _tracing.spans_recent()
    if spans:
        # the span rings ride every dump as ndjson (one span per line,
        # joinable against the events' trace_id fields) — a post-mortem
        # of a failover carries the request trees that crossed it
        span_path = os.path.join(
            directory, "spans-%d-%04d-%s.ndjson"
            % (os.getpid(), seq, safe_reason))
        span_blob = "".join(json.dumps(s, default=str) + "\n"
                            for s in spans)
        atomic_write(span_path, lambda tmp: _write_text(tmp, span_blob),
                     durable=False)
        payload["span_dump"] = span_path
    path = os.path.join(directory, "flightrec-%d-%04d-%s.json"
                        % (os.getpid(), seq, safe_reason))
    blob = json.dumps(payload, indent=1, default=str)
    # durable=False: the dump races process death by design — atomic
    # against a torn write, but an fsync stall must not eat the drain
    # window
    atomic_write(path, lambda tmp: _write_text(tmp, blob), durable=False)
    _telemetry.event("flight_recorder.dump", reason=reason, path=path)
    _log.warning("perfdebug: flight recorder dumped to %s (reason=%s)",
                 path, reason)
    return path


def reset():
    """Clear attribution entries, change log and the flight ring
    (tests; enablement is unchanged)."""
    global _latest_step
    with _lock:
        _entries.clear()
        _changes.clear()
        _latest_step = None
    with _flight_lock:
        _flight.clear()
