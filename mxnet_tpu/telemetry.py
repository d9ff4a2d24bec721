"""Telemetry — the process-wide metrics registry + structured event log.

The reference stack's observability is three disconnected point tools
(the chrome-trace profiler, the per-tensor ``Monitor``, the
``Speedometer`` log line); the TensorFlow system paper instead treats
run-level metrics and tracing as a first-class subsystem.  This module
is that subsystem for the TPU framework: every layer (``Module.fit``
phase timing, KVStore transport, XLA compile tracking, resilience
events, device memory) reports into ONE thread-safe registry, exposed as

* ``snapshot()``   — nested dict (counters / gauges / histograms / events)
* ``dump(path)``   — the snapshot as JSON
* ``dump_events(path)`` — the structured event log as JSONL
* ``prometheus_text()`` / ``write_prometheus(path)`` — Prometheus
  text-exposition format (``mxnet_``-prefixed metric names)

Cost model (the ``profiler.span.__init__`` trick): telemetry is OFF by
default and every recording call checks one module-level boolean first,
so a disabled counter bump is a single early-returning function call and
a disabled :class:`phase` timer does no clock reads — instrumentation
stays compiled into production hot paths at effectively zero cost
(tests/test_telemetry.py pins the per-batch overhead).

Enable with ``MXNET_TELEMETRY=1`` (or :func:`enable`).  Setting
``MXNET_TELEMETRY_DUMP=path`` implies enablement and atexit-writes the
snapshot JSON to ``path`` plus the event log to
``<path-sans-ext>.events.jsonl``.

Metric names are dotted families (``fit.*``, ``kvstore.*``, ``xla.*``,
``resilience.*``, ``elastic.*``, ``memory.*``, ``serving.*`` —
including the paged-KV occupancy gauges under ``serving.kv.*``); labels
are free-form keyword arguments (``inc("kvstore.push.count",
server=0)``).
"""

from __future__ import annotations

import bisect
import contextlib
import json
import os
import re
import threading
import time
from collections import deque

from . import profiler as _profiler
from . import tracing as _tracing

__all__ = ["enabled", "enable", "disable", "inc", "tap", "replay", "declare",
           "set_gauge",
           "observe", "event", "phase", "snapshot", "dump", "dump_events",
           "prometheus_text", "write_prometheus", "reset", "sample_memory",
           "phase_totals", "counter_total", "gauge_value", "hist_quantile",
           "hist_state", "quantile_from_counts", "events_recent",
           "add_phase_hook", "remove_phase_hook",
           "aggregate", "start_exporter", "stop_exporter",
           "exporter_running"]

#: default histogram bucket upper bounds (seconds-flavored; callers may
#: pass their own on first ``observe`` of a metric)
DEFAULT_BUCKETS = (1e-5, 1e-4, 1e-3, 1e-2, 0.1, 1.0, 10.0, 60.0)

_lock = threading.Lock()
_counters = {}   # (name, labels) -> float
_gauges = {}     # (name, labels) -> float
_hists = {}      # (name, labels) -> _Histogram
_events = deque(maxlen=int(os.environ.get("MXNET_TELEMETRY_EVENTS_MAX",
                                          "10000")))

_enabled = (os.environ.get("MXNET_TELEMETRY", "0")
            not in ("0", "", "false")
            or bool(os.environ.get("MXNET_TELEMETRY_DUMP"))
            # an armed flight recorder (perfdebug) implies telemetry:
            # its dumps are built from the event ring and phase timings,
            # so a recorder without telemetry would dump hollow files
            # exactly when the post-mortem needs them
            or os.environ.get("MXNET_FLIGHT_RECORDER", "")
            not in ("0", "", "false")
            or bool(os.environ.get("MXNET_FLIGHT_RECORDER_DIR"))
            # an armed hang watchdog (sentinel) implies telemetry the
            # same way: its whole progress feed is the phase hook, and
            # phase exits only reach hooks while telemetry records — a
            # watchdog without telemetry would see a healthy job as
            # eternally stalled and false-trip at the deadline floor
            or os.environ.get("MXNET_WATCHDOG", "")
            not in ("0", "", "false")
            # an armed fleet exporter implies telemetry: its whole
            # output is this registry's snapshot, so an export dir over
            # a disabled registry would publish empty files forever
            or bool(os.environ.get("MXNET_TELEMETRY_EXPORT_DIR")))


def enabled():
    """True when the registry records (``MXNET_TELEMETRY=1`` or
    :func:`enable`); the one check every hot path makes."""
    return _enabled


def enable():
    global _enabled
    _enabled = True


def disable():
    global _enabled
    _enabled = False


def _key(name, labels):
    if not labels:
        return (name, ())
    return (name, tuple(sorted((k, str(v)) for k, v in labels.items())))


#: counter keys declared at zero (``inc(name, 0)``) — remembered across
#: :func:`reset` so an enabled-mode reset (the exporter keeps running,
#: a test clears mid-run) re-seeds the declared families instead of
#: silently dropping them from ``snapshot()``/Prometheus until their
#: next increment
_declared = set()


# -- recording --------------------------------------------------------------
def inc(name, value=1, **labels):
    """Add ``value`` to counter ``name`` (``inc(name, 0)`` declares it at
    zero so a family is visible in ``snapshot()`` before its first
    increment)."""
    if not _enabled:
        return
    k = _key(name, labels)
    heard = getattr(_tap, "counts", None)
    if heard is not None and value:
        heard[k] = heard.get(k, 0) + value
    with _lock:
        if value == 0:
            _declared.add(k)
        _counters[k] = _counters.get(k, 0) + value


_tap = threading.local()  # .counts: what tap() hears on this thread


@contextlib.contextmanager
def tap():
    """Hear every :func:`inc` this thread makes inside the block: yields
    the dict ``{(name, labels): sum}`` they add up in, which
    :func:`replay` counts again.  How what a trace counts
    (``ops.kernel_path``) is kept beside the executable it made
    (``compile_cache``'s store), for the start that loads the executable
    and traces nothing.  Empty while the registry is off."""
    outer, _tap.counts = getattr(_tap, "counts", None), {}
    try:
        yield _tap.counts
    finally:
        _tap.counts = outer


def replay(counts):
    """Count again what a :func:`tap` heard."""
    for (name, labels), value in counts.items():
        inc(name, value, **dict(labels))


def declare(*names):
    """Declare counter families at zero so they are visible in
    ``snapshot()``/Prometheus before their first increment (``fit``
    does this for the resilience family; ``compile_cache`` for the
    persistent-cache family)."""
    for name in names:
        inc(name, 0)


def set_gauge(name, value, **labels):
    """Set gauge ``name`` to ``value`` (last write wins)."""
    if not _enabled:
        return
    with _lock:
        _gauges[_key(name, labels)] = value


class _Histogram:
    __slots__ = ("buckets", "counts", "count", "sum", "min", "max")

    def __init__(self, buckets=DEFAULT_BUCKETS):
        self.buckets = tuple(buckets)
        self.counts = [0] * (len(self.buckets) + 1)  # +1: overflow (+Inf)
        self.count = 0
        self.sum = 0.0
        self.min = None
        self.max = None

    def observe(self, v):
        v = float(v)
        self.counts[bisect.bisect_left(self.buckets, v)] += 1
        self.count += 1
        self.sum += v
        if self.min is None or v < self.min:
            self.min = v
        if self.max is None or v > self.max:
            self.max = v


def observe(name, value, buckets=None, **labels):
    """Record ``value`` into histogram ``name`` (bucket bounds fixed by
    the first observation)."""
    if not _enabled:
        return
    k = _key(name, labels)
    with _lock:
        h = _hists.get(k)
        if h is None:
            h = _hists[k] = _Histogram(buckets or DEFAULT_BUCKETS)
        h.observe(value)


def event(name, **fields):
    """Append one structured event (``{"ts", "event", **fields}``) to the
    in-memory JSONL log (bounded ring; ``dump_events`` exports)."""
    if not _enabled:
        return
    rec = {"ts": round(time.time(), 6), "event": name}
    rec.update(fields)
    with _lock:
        _events.append(rec)


def events_recent(n=100):
    """The newest ``n`` structured events (copies) — what the flight
    recorder folds into a crash dump."""
    with _lock:
        return [dict(r) for r in list(_events)[-int(n):]]


#: registered per-phase observers, each called as ``hook(family,
#: phase_name, seconds)`` from an ENABLED phase's exit.  Two consumers
#: exist today — the flight recorder's per-batch timing feed
#: (:mod:`mxnet_tpu.perfdebug`) and the training watchdog's progress
#: feed (:mod:`mxnet_tpu.sentinel`) — which is exactly why this is a
#: LIST.  Stored as a tuple so the hot path iterates a stable snapshot
#: (one truthiness check when empty); registration swaps the whole
#: tuple under ``_lock``.
_phase_hooks = ()


def add_phase_hook(hook):
    """Register a phase observer (``hook(family, phase, seconds)``);
    duplicate registrations are ignored.  Returns ``hook`` so callers
    can hold it for :func:`remove_phase_hook`."""
    global _phase_hooks
    with _lock:
        if hook not in _phase_hooks:
            _phase_hooks = _phase_hooks + (hook,)
    return hook


def remove_phase_hook(hook):
    """Unregister a phase observer; unknown hooks are a no-op."""
    global _phase_hooks
    with _lock:
        _phase_hooks = tuple(h for h in _phase_hooks if h is not hook)


class phase:
    """One training-loop phase: the span ``<family>.<name>`` (child of
    the thread's current span; :mod:`mxnet_tpu.tracing` times it, on its
    one clock, and puts it into a device profile as
    ``mx.<family>.<name>``), a histogram observation in
    ``<family>.phase_seconds{phase=<name>}``, the phase hooks and — when
    ``mx.profiler`` is running — a ``<family>:<name>`` slice of its
    chrome-trace dump.

    Disabled-cheap like ``profiler.span``: the enabled checks happen
    once and a disabled phase does no clock reads.  Note JAX
    dispatch is asynchronous, so device compute time is attributed to the
    first phase that blocks on results (see docs/observability.md) — in
    the sync-free fit loop that is the explicit ``sync`` phase (device
    metric reads, NaN-guard flag reads), which exists precisely so
    ``metric`` and friends time only their dispatch work.
    """

    __slots__ = ("_name", "_family", "_span", "_on", "_prof")

    def __init__(self, name, family="fit"):
        self._prof = _profiler.running()
        self._on = _enabled or self._prof
        self._name = name
        self._family = family

    def __enter__(self):
        self._span = _tracing.start_span(
            "%s.%s" % (self._family, self._name), loop=True,
            timed=True) if self._on or _tracing.enabled() \
            else _tracing.NULL_SPAN
        return self

    def __exit__(self, exc_type, exc, tb):
        sp = self._span
        sp.end("ok" if exc_type is None else "error")
        if self._on:
            dt = sp.dur_s
            if _enabled:
                observe(self._family + ".phase_seconds", dt,
                        phase=self._name)
            if self._prof:
                end = _profiler._now_us()
                _profiler.record("%s:%s" % (self._family, self._name),
                                 "phase", end - dt * 1e6, end)
            if _phase_hooks:
                for hook in _phase_hooks:
                    hook(self._family, self._name, dt)
        return False


# -- derived reads ----------------------------------------------------------
def phase_totals(family="fit"):
    """``{phase: (sum_seconds, count)}`` for one family's phase
    histograms — the per-phase step-time breakdown consumers
    (``TelemetryReport``, the flight recorder) read."""
    name = family + ".phase_seconds"
    out = {}
    with _lock:
        for (n, labels), h in _hists.items():
            if n == name:
                out[dict(labels).get("phase", "")] = (h.sum, h.count)
    return out


def counter_total(name):
    """Sum of counter ``name`` across all label sets (0 when absent)."""
    with _lock:
        return sum(v for (n, _), v in _counters.items() if n == name)


def gauge_value(name, **labels):
    """Current value of gauge ``name`` (None when unset)."""
    with _lock:
        return _gauges.get(_key(name, labels))


def hist_quantile(name, q, **labels):
    """Estimate the ``q``-quantile (0..1) of histogram ``name`` from its
    bucket counts — linear interpolation inside the target bucket, the
    observed min/max capping the first/overflow buckets.  What the
    serving layer's p50/p99 reads (and Prometheus' ``histogram_quantile``
    would compute from the same exposition); None when unobserved."""
    with _lock:
        h = _hists.get(_key(name, labels))
        if h is None or h.count == 0:
            return None
        target = q * h.count
        acc = 0
        lo = h.min
        for b, c in zip(h.buckets, h.counts):
            if acc + c >= target:
                if c == 0:
                    return min(lo, h.max)
                frac = (target - acc) / c
                return min(lo + (min(b, h.max) - lo) * max(0.0, frac),
                           h.max)
            acc += c
            lo = max(lo, b)
        return h.max  # overflow bucket: cap at the observed max


def hist_state(name, **labels):
    """Raw histogram state — bucket bounds, per-bucket counts (the last
    entry is the overflow bucket), total count/sum and observed min/max
    — or None when unobserved.  Windowed-quantile readers (the fleet
    controller's TTFT-p99 window) diff two snapshots' counts and feed
    the delta to :func:`quantile_from_counts`; cumulative
    :func:`hist_quantile` would smear the whole process history into
    the estimate."""
    with _lock:
        h = _hists.get(_key(name, labels))
        if h is None:
            return None
        return {"buckets": tuple(h.buckets), "counts": list(h.counts),
                "count": h.count, "sum": h.sum,
                "min": h.min, "max": h.max}


def quantile_from_counts(buckets, counts, q, lo=None, hi=None):
    """:func:`hist_quantile`'s estimator over caller-supplied bucket
    counts (e.g. the delta of two :func:`hist_state` reads).  ``lo`` /
    ``hi`` cap the first/overflow buckets the way the histogram's
    observed min/max do; they default to 0 and the last finite bound.
    None when the counts are empty."""
    total = sum(counts)
    if total <= 0:
        return None
    lo = 0.0 if lo is None else float(lo)
    hi = float(buckets[-1]) if hi is None else float(hi)
    target = q * total
    acc = 0
    cur = lo
    for b, c in zip(buckets, counts):
        if acc + c >= target:
            if c == 0:
                return min(cur, hi)
            frac = (target - acc) / c
            return min(cur + (min(b, hi) - cur) * max(0.0, frac), hi)
        acc += c
        cur = max(cur, b)
    return hi  # overflow bucket: cap at hi


# -- memory sampling --------------------------------------------------------
def sample_memory():
    """Sample device (HBM) memory stats from JAX into ``memory.device.*``
    gauges, plus the host max-RSS so the memory family exists even on
    backends (CPU) whose devices expose no ``memory_stats``."""
    if not _enabled:
        return
    try:
        import jax

        devices = jax.local_devices()
    except (ImportError, RuntimeError):
        devices = []
    for d in devices:
        stats_fn = getattr(d, "memory_stats", None)
        stats = None
        if stats_fn is not None:
            try:
                stats = stats_fn()
            except (RuntimeError, NotImplementedError):
                stats = None  # backend without allocator stats
        if not stats:
            continue
        for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            if k in stats:
                set_gauge("memory.device.%s" % k, stats[k],
                          device=getattr(d, "id", 0))
    try:
        import resource
        import sys

        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        # ru_maxrss unit is kilobytes on Linux but bytes on macOS
        if sys.platform != "darwin":
            rss *= 1024
        set_gauge("memory.host.max_rss_bytes", rss)
    except (ImportError, ValueError, OSError):  # non-POSIX host
        pass


# -- exporters --------------------------------------------------------------
def _label_str(labels):
    return ",".join("%s=%s" % kv for kv in labels)


def _hist_dict(h):
    cum, acc = {}, 0
    for b, c in zip(h.buckets, h.counts):
        acc += c
        cum["%g" % b] = acc
    cum["+Inf"] = acc + h.counts[-1]
    return {"count": h.count, "sum": h.sum, "min": h.min, "max": h.max,
            "mean": (h.sum / h.count) if h.count else 0.0, "buckets": cum}


def snapshot():
    """The whole registry as a nested dict:
    ``{enabled, counters: {name: {labels: v}}, gauges: {...},
    histograms: {name: {labels: {count,sum,min,max,mean,buckets}}},
    events: {count, recent}}``."""
    with _lock:
        counters, gauges, hists = {}, {}, {}
        for (n, labels), v in sorted(_counters.items()):
            counters.setdefault(n, {})[_label_str(labels)] = v
        for (n, labels), v in sorted(_gauges.items()):
            gauges.setdefault(n, {})[_label_str(labels)] = v
        for (n, labels), h in sorted(_hists.items()):
            hists.setdefault(n, {})[_label_str(labels)] = _hist_dict(h)
        return {"enabled": _enabled, "counters": counters, "gauges": gauges,
                "histograms": hists,
                "events": {"count": len(_events),
                           "recent": list(_events)[-100:]}}


def dump(path):
    """Write ``snapshot()`` as JSON; returns ``path``."""
    with open(path, "w") as f:
        json.dump(snapshot(), f, indent=1, default=str)
    return path


def dump_events(path):
    """Write the structured event log as JSONL (one event per line);
    returns ``path``."""
    with _lock:
        events = list(_events)
    with open(path, "w") as f:
        for rec in events:
            f.write(json.dumps(rec, default=str))
            f.write("\n")
    return path


def _prom_name(name):
    s = re.sub(r"[^a-zA-Z0-9_]", "_", name)
    return s if s.startswith("mxnet_") else "mxnet_" + s


def _prom_esc(v):
    return str(v).replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n")


def _prom_labels(labels, extra=()):
    items = list(labels) + list(extra)
    if not items:
        return ""
    return "{%s}" % ",".join('%s="%s"' % (k, _prom_esc(v))
                             for k, v in items)


def _prom_num(v):
    v = float(v)
    return "%d" % int(v) if v.is_integer() else repr(v)


def _parse_label_str(s):
    """Invert :func:`_label_str`: ``"a=1,b=x"`` -> ``[("a","1"),
    ("b","x")]`` (the snapshot's label encoding, shared by
    :func:`aggregate` and the Prometheus renderer)."""
    if not s:
        return []
    out = []
    for part in s.split(","):
        k, _, v = part.partition("=")
        out.append((k, v))
    return out


def _bucket_order(bound):
    return float("inf") if bound == "+Inf" else float(bound)


def prometheus_text(snap=None):
    """The registry — or any :func:`snapshot`/:func:`aggregate`-shaped
    dict passed as ``snap`` — in Prometheus text-exposition format
    (counter / gauge / histogram types, cumulative ``le`` buckets)."""
    if snap is None:
        snap = snapshot()
    lines = []
    for kind, store in (("counter", snap.get("counters", {})),
                        ("gauge", snap.get("gauges", {}))):
        for name in sorted(store):
            pname = _prom_name(name)
            lines.append("# TYPE %s %s" % (pname, kind))
            for lstr in sorted(store[name]):
                lines.append("%s%s %s" % (
                    pname, _prom_labels(_parse_label_str(lstr)),
                    _prom_num(store[name][lstr])))
    for name in sorted(snap.get("histograms", {})):
        pname = _prom_name(name)
        lines.append("# TYPE %s histogram" % pname)
        for lstr in sorted(snap["histograms"][name]):
            h = snap["histograms"][name][lstr]
            labels = _parse_label_str(lstr)
            for b in sorted(h["buckets"], key=_bucket_order):
                lines.append("%s_bucket%s %d" % (
                    pname, _prom_labels(labels, [("le", b)]),
                    h["buckets"][b]))
            lines.append("%s_sum%s %s" % (pname, _prom_labels(labels),
                                          _prom_num(h["sum"])))
            lines.append("%s_count%s %d" % (pname, _prom_labels(labels),
                                            h["count"]))
    return "\n".join(lines) + "\n"


def write_prometheus(path):
    """Write :func:`prometheus_text` to ``path`` (e.g. for a node-exporter
    textfile collector); returns ``path``."""
    with open(path, "w") as f:
        f.write(prometheus_text())
    return path


# -- fleet aggregation -------------------------------------------------------
def _merge_hists(dicts):
    """Merge several :func:`_hist_dict`-shaped histograms bucket-wise:
    each cumulative bucket series is decomposed into per-bucket counts,
    summed over the union of bounds, and re-accumulated — so a fleet
    quantile comes from MERGED buckets, not an average of per-process
    quantiles."""
    bounds = sorted({_bucket_order(b) for d in dicts
                     for b in d.get("buckets", {}) if b != "+Inf"})
    idx = {b: i for i, b in enumerate(bounds)}
    per = [0] * (len(bounds) + 1)   # +1: overflow
    count, total = 0, 0.0
    mn = mx = None
    for d in dicts:
        cum = d.get("buckets", {})
        prev = 0
        for b in sorted((b for b in cum if b != "+Inf"),
                        key=_bucket_order):
            per[idx[_bucket_order(b)]] += cum[b] - prev
            prev = cum[b]
        per[-1] += cum.get("+Inf", prev) - prev
        count += d.get("count", 0)
        total += d.get("sum", 0.0)
        if d.get("min") is not None:
            mn = d["min"] if mn is None else min(mn, d["min"])
        if d.get("max") is not None:
            mx = d["max"] if mx is None else max(mx, d["max"])
    merged, acc = {}, 0
    for b, c in zip(bounds, per[:-1]):
        acc += c
        merged["%g" % b] = acc
    merged["+Inf"] = acc + per[-1]
    return {"count": count, "sum": total, "min": mn, "max": mx,
            "mean": (total / count) if count else 0.0, "buckets": merged}


def aggregate(directory=None, snapshots=None, include_local=False):
    """Merge several processes' registries into ONE snapshot-shaped
    dict (renderable by ``prometheus_text(snap)``):

    * **counters** are summed per (family, label set) — fleet totals;
    * **gauges** keep one entry per process, the label set extended
      with ``proc=<name>`` (a gauge is a state, not a flow: summing
      two replicas' ``slot_occupancy`` would fabricate a third state);
    * **histograms** merge bucket-wise (:func:`_merge_hists`) so fleet
      quantiles come from combined buckets;
    * **events** concatenate (each tagged with its ``proc``), newest
      last, bounded to the per-process ring size.

    Sources: every ``*.telemetry.json`` under ``directory`` (the
    :func:`start_exporter` layout; torn or garbled files are skipped —
    they lose one cadence, not the merge), plus any pre-loaded
    ``snapshots`` dicts, plus this process's live registry when
    ``include_local`` (tagged ``proc=local`` unless the exporter names
    it).  Returns ``{"procs": [...], "counters", "gauges",
    "histograms", "events"}``."""
    snaps = list(snapshots or ())
    local_proc = _exporter.proc if _exporter is not None else "local"
    if directory:
        try:
            names = sorted(os.listdir(directory))
        except OSError:
            names = []
        for fn in names:
            if not fn.endswith(".telemetry.json"):
                continue
            # include_local reads THIS process from its live registry;
            # its own (staler) export file must not double-count it
            if include_local \
                    and fn == "%s.telemetry.json" % local_proc:
                continue
            try:
                with open(os.path.join(directory, fn)) as f:
                    snaps.append(json.load(f))
            except (OSError, ValueError):
                continue
    if include_local:
        snaps.append(dict(snapshot(), proc=local_proc))
    procs, counters, gauges, hist_parts = [], {}, {}, {}
    events = []
    for i, s in enumerate(snaps):
        proc = str(s.get("proc") or "p%d" % i)
        procs.append(proc)
        for name, by_label in s.get("counters", {}).items():
            dst = counters.setdefault(name, {})
            for lstr, v in by_label.items():
                dst[lstr] = dst.get(lstr, 0) + v
        for name, by_label in s.get("gauges", {}).items():
            dst = gauges.setdefault(name, {})
            for lstr, v in by_label.items():
                dst[(lstr + "," if lstr else "") + "proc=" + proc] = v
        for name, by_label in s.get("histograms", {}).items():
            dst = hist_parts.setdefault(name, {})
            for lstr, h in by_label.items():
                dst.setdefault(lstr, []).append(h)
        recent = s.get("events", {}).get("recent", [])
        events.extend(dict(r, proc=proc) for r in recent)
    hists = {name: {lstr: _merge_hists(parts)
                    for lstr, parts in by_label.items()}
             for name, by_label in hist_parts.items()}
    events.sort(key=lambda r: r.get("ts", 0))
    events = events[-_events.maxlen:]
    return {"enabled": True, "procs": procs, "counters": counters,
            "gauges": gauges, "histograms": hists,
            "events": {"count": len(events), "recent": events}}


# -- fleet export ------------------------------------------------------------
class _Exporter(threading.Thread):
    """Cadence thread publishing this process's registry as an atomic
    snapshot file ``<proc>.telemetry.json`` under the export dir — the
    same one-file-per-member layout as ``tools/supervise.py``'s
    heartbeat dir, so a supervised fleet's telemetry and liveness live
    side by side."""

    def __init__(self, directory, interval, proc):
        super().__init__(name="telemetry-export", daemon=True)
        self.directory = directory
        self.interval = interval
        self.proc = proc
        self.path = os.path.join(directory, "%s.telemetry.json" % proc)
        self._stop_ev = threading.Event()

    def write_once(self):
        """One atomic snapshot publish; never raises (a full disk
        loses one cadence, not the process)."""
        from .base import atomic_write

        payload = dict(snapshot(), proc=self.proc, pid=os.getpid(),
                       export_ts=round(time.time(), 6))
        blob = json.dumps(payload, default=str)

        def _w(tmp):
            with open(tmp, "w") as f:
                f.write(blob)

        try:
            # durable=False: the cadence republishes in seconds; an
            # fsync stall on a loaded host must not back up the fleet
            atomic_write(self.path, _w, durable=False)
        except OSError:
            pass

    def run(self):
        while not self._stop_ev.wait(self.interval):
            self.write_once()
        self.write_once()   # final publish: exit totals are visible

    def stop(self, timeout=5.0):
        self._stop_ev.set()
        self.join(timeout)


_exporter = None


def start_exporter(directory=None, interval_s=None, proc=None):
    """Arm the fleet export thread (idempotent: a live exporter is
    returned as-is, so repeated arming — or a :func:`reset` — can
    never stack cadence threads).  Defaults come from
    ``MXNET_TELEMETRY_EXPORT_DIR`` / ``_INTERVAL_S`` / ``_PROC``;
    implies :func:`enable` and writes the first snapshot immediately
    (a just-launched worker is visible before its first cadence).
    Also registers a final atexit publish."""
    global _exporter
    if _exporter is not None and _exporter.is_alive():
        return _exporter
    directory = directory or os.environ.get("MXNET_TELEMETRY_EXPORT_DIR")
    if not directory:
        raise ValueError("start_exporter needs a directory (or "
                         "MXNET_TELEMETRY_EXPORT_DIR)")
    if interval_s is None:
        try:
            interval_s = float(os.environ.get(
                "MXNET_TELEMETRY_EXPORT_INTERVAL_S", "2.0") or 2.0)
        except ValueError:
            interval_s = 2.0
    proc = proc or os.environ.get("MXNET_TELEMETRY_EXPORT_PROC") \
        or "pid%d" % os.getpid()
    enable()
    os.makedirs(directory, exist_ok=True)
    _exporter = _Exporter(directory, max(0.05, float(interval_s)), proc)
    _exporter.write_once()
    _exporter.start()
    import atexit

    atexit.register(_atexit_export)
    return _exporter


def _atexit_export():  # pragma: no cover - exercised via subprocess test
    if _exporter is not None and _exporter.is_alive():
        _exporter.stop()


def stop_exporter():
    """Stop the export thread (final snapshot included); no-op when
    none is armed."""
    global _exporter
    exp, _exporter = _exporter, None
    if exp is not None and exp.is_alive():
        exp.stop()


def exporter_running():
    """True while the cadence thread is alive (the reset-audit test's
    leak probe)."""
    return _exporter is not None and _exporter.is_alive()


def reset():
    """Clear all metrics and events (tests; enablement is unchanged).

    While ENABLED, counter families declared at zero (``inc(name,
    0)``) are re-seeded rather than dropped — a mid-run reset under a
    live exporter must not make declared families vanish from the
    exposition until their next increment.  A disabled reset clears
    everything (the test fixtures' teardown path).  The export thread,
    if armed, is left running: it publishes whatever the registry
    holds and is stopped only by :func:`stop_exporter`."""
    with _lock:
        _counters.clear()
        _gauges.clear()
        _hists.clear()
        _events.clear()
        if _enabled:
            for k in _declared:
                _counters[k] = 0


def _atexit_dump():  # pragma: no cover - exercised via subprocess test
    path = os.environ.get("MXNET_TELEMETRY_DUMP")
    if not path:
        return
    try:
        dump(path)
        dump_events(os.path.splitext(path)[0] + ".events.jsonl")
    except OSError as e:
        import logging

        logging.warning("telemetry: could not write %r at exit: %s",
                        path, e)


if os.environ.get("MXNET_TELEMETRY_DUMP"):
    import atexit

    atexit.register(_atexit_dump)

if os.environ.get("MXNET_TELEMETRY_EXPORT_DIR"):
    # env-armed fleet export: the process publishes itself from import
    # on, no call site needed (supervised children get the dir from
    # tools/supervise.py --telemetry-dir)
    start_exporter()
