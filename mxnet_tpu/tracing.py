"""graftrace — distributed request/step tracing.

The stack is a distributed system — replica pools migrate generation
sessions across engines (PR 12), elastic KVStore jobs reshard across
worker processes (PR 11), supervised fleets restart members under a
harness (PR 15/16) — but until now no identifier survived a hop: a
generation that failed over mid-decode, or a reshard cycle spanning
four workers, could not be reconstructed after the fact.  This module
mints a ``trace_id``/``span_id`` at every entry point (HTTP request,
batcher submit, decode session, ``fit`` batch, checkpoint write,
elastic reshard) and carries it through routing → dispatch → failover
→ resume, and over the KVStore wire (an optional ``trace`` field on
push/pull/barrier/reshard verbs) so worker↔coordinator spans stitch
into one tree.

Span model (a deliberately small slice of the OpenTelemetry shape):

* a **trace** is one request/step's causal tree, identified by a
  16-hex ``trace_id``;
* a **span** is one timed operation inside it — 8-hex ``span_id``,
  ``parent_id`` link, ``t0_ns``/``t1_ns`` on ``time.monotonic_ns()``
  (ONE clock: the one ``GenerateSession.t_submit`` and the benchmark's
  window use), the wall-clock ``t0``/``t1`` and ``dur_s`` derived from
  them, the opening thread's ``tid``, free-form ``attrs``, and a typed
  ``status``: ``ok`` / ``shed`` / ``migrated`` / ``retry`` / ``error``
  (``in_flight`` for live spans in a :func:`tree` read);
* parenting is implicit on one thread (a thread-local span stack) and
  explicit across threads/processes (``parent=`` a :class:`Span`, or
  ``trace_id=``/``parent_id=`` from a wire context).

Two sinks.  Finished spans land in a bounded ring (``MXNET_TRACE_RING``,
default 4096) that the flight recorder dumps as ndjson
(``spans-<pid>-<seq>-<reason>.ndjson``) and ``GET /trace/<id>`` on the
serving frontend assembles — live spans included — via :func:`tree`;
spans whose root is a loop iteration (``loop=True``: a ``fit`` batch, a
decode-engine iteration, a lone phase) go to a second ring of the same
bound, so a busy loop never shortens how long a request's tree stays
readable, and the spans of :func:`setup_span` (recorded with tracing off)
to a third, read by :func:`setup_spans`.  And a span opened with
``stack=True`` — opened and closed on one thread — is also a
``jax.profiler.TraceAnnotation`` named ``mx.<name>`` while a device
profile is being taken, so it lies in the
``.xplane.pb`` beside the device's operations, on the profiler's own
clock (event times there are relative to the session's start: no Python
clock can be matched to them afterwards).

Cost model (the PR 2 discipline): tracing is OFF by default and turns
itself ON while somebody profiles the device.  :func:`start_span` checks
one module bool and ``TraceAnnotation.is_enabled()`` (a static call)
first, returning the shared falsy :data:`NULL_SPAN` — a disabled entry
point pays one call and two branches, no clock read, no allocation.
Enable with ``MXNET_TRACE=1`` (or :func:`enable`), or start a
``jax.profiler`` trace; tests/test_tracing.py and
tests/test_tracing_profile.py pin the disabled overhead.  The one
exception is :func:`setup_span`, which always records: a dozen spans a
start (the import, an engine's build and each program's first call, a
module's ``bind``), none in a loop, so that set-up, which runs before
anything is enabled, can be accounted for afterwards.

See docs/observability.md "Distributed tracing & fleet aggregation".
"""

from __future__ import annotations

import contextlib
import os
import random
import threading
import time
from collections import deque

from jax.profiler import TraceAnnotation as _Annotation

__all__ = ["enabled", "enable", "disable", "start_span", "setup_span",
           "host_read", "frame", "current", "ctx", "tree", "spans_recent",
           "setup_spans", "reset", "Span", "NULL_SPAN", "STATUSES"]

#: the typed span statuses (``in_flight`` is synthesized for live
#: spans in :func:`tree` reads, never stored)
STATUSES = ("ok", "shed", "migrated", "retry", "error")

#: wall-clock nanoseconds at monotonic zero, taken once: every wall time
#: a record shows is its monotonic time plus this
_WALL_NS = time.time_ns() - time.monotonic_ns()

#: True while a ``jax.profiler`` session is live in this process (a
#: static call): what switches the spans on by themselves
_profile_live = _Annotation.is_enabled


def _ring_size():
    try:
        return max(64, int(os.environ.get("MXNET_TRACE_RING", "") or 4096))
    except ValueError:
        return 4096


_lock = threading.Lock()
_ring = deque(maxlen=_ring_size())   # finished spans, oldest first
_loop_ring = deque(maxlen=_ring_size())  # ... of traces a loop rooted
_setup_ring = deque(maxlen=_ring_size())  # ... opened by setup_span
_live = {}                           # span_id -> Span (in flight)
_tls = threading.local()

_enabled = os.environ.get("MXNET_TRACE", "0") not in ("0", "", "false")


def enabled():
    """True when spans record (``MXNET_TRACE=1``, :func:`enable`, or a
    device profile being taken); the check every entry point makes."""
    return _enabled or _profile_live()


def enable():
    global _enabled
    _enabled = True


def disable():
    global _enabled
    _enabled = False


#: ids come from a generator seeded by the OS once (and again in a forked
#: child): ``os.urandom`` per span is a system call per span
_rand = random.Random()
os.register_at_fork(after_in_child=_rand.seed)


def _new_id(nbytes):
    return "%0*x" % (2 * nbytes, _rand.getrandbits(8 * nbytes))


def _stack():
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
        _tls.tid = threading.get_native_id()
    return st


def _wall(ns):
    return round((ns + _WALL_NS) * 1e-9, 6)


class _NullSpan:
    """The falsy no-op span a disabled :func:`start_span` returns:
    every method is a pass, so instrumented code needs no enablement
    branches of its own."""

    __slots__ = ()
    trace_id = None
    span_id = None
    parent_id = None

    def __bool__(self):
        return False

    def annotate(self, **attrs):
        pass

    def end(self, status="ok", **attrs):
        pass

    def drop(self):
        pass

    def ctx(self):
        return None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


#: the shared disabled-mode span (one allocation per process)
NULL_SPAN = _NullSpan()


class Span:
    """One live span.  Create via :func:`start_span`; finish EXACTLY
    once via :meth:`end` (idempotent — a second call is ignored, so a
    failover path and a late resolve cannot double-record)."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "t0_ns",
                 "t1_ns", "tid", "status", "attrs", "_stacked", "_loop",
                 "_setup", "_recorded", "_ann", "_ended")

    def __init__(self, name, trace_id, parent_id, loop=False,
                 recorded=True, setup=False):
        self.name = name
        self.trace_id = trace_id
        self.span_id = _new_id(4) if recorded else None
        self.parent_id = parent_id
        self.t1_ns = None
        self.status = None
        self.attrs = {}
        self._stacked = False
        self._loop = loop
        self._setup = setup
        self._recorded = recorded
        self._ann = None
        self._ended = False
        self.tid = None
        self.t0_ns = time.monotonic_ns()

    def __bool__(self):
        return True

    @property
    def dur_s(self):
        """Seconds from open to :meth:`end` (None while live)."""
        return None if self.t1_ns is None \
            else (self.t1_ns - self.t0_ns) * 1e-9

    def annotate(self, **attrs):
        """Attach attributes to a live span (last write per key wins)."""
        self.attrs.update(attrs)

    def ctx(self):
        """The wire context: ``{"trace_id", "span_id"}`` — what a
        KVStore message or a cross-process hand-off carries so the
        remote side can parent its span here."""
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    def _record(self, live=False):
        """The span as the dict every reader gets (built on the read, not
        on the hot path that ends the span)."""
        t0, t1 = self.t0_ns, self.t1_ns
        return {"trace_id": self.trace_id, "span_id": self.span_id,
                "parent_id": self.parent_id, "name": self.name,
                "t0": _wall(t0), "t1": None if t1 is None else _wall(t1),
                "dur_s": None if t1 is None
                else round((t1 - t0) * 1e-9, 6),
                "t0_ns": t0, "t1_ns": t1, "tid": self.tid,
                "status": "in_flight" if live else self.status,
                "attrs": dict(self.attrs)}

    def _home(self):
        """The finished ring this span belongs in (lock held)."""
        return _setup_ring if self._setup \
            else _loop_ring if self._loop else _ring

    def end(self, status="ok", **attrs):
        """Finish the span with a typed ``status``; moves it from the
        live set into its bounded finished ring and closes its
        annotation in the device profile."""
        self._finish(status, attrs)

    def drop(self):
        """Forget a span that turned out to cover no work (an idle loop
        iteration): it leaves the live set and the thread's stack and
        nothing is recorded."""
        self._finish(None, None)

    def _finish(self, status, attrs):
        t1 = time.monotonic_ns()
        if not self._recorded:      # a bare timer: nobody else holds it
            self.t1_ns = t1
            return
        with _lock:
            if self._ended:
                return
            self._ended = True
            self.t1_ns = t1
            self.status = status
            if attrs:
                self.attrs.update(attrs)
            _live.pop(self.span_id, None)
            if status is not None:
                self._home().append(self)
        if self._stacked:
            st = getattr(_tls, "stack", None)
            # only pop when ending on the opening thread with this
            # span on top — a cross-thread end (failover resolve) must
            # not corrupt another thread's stack
            if st and st[-1] is self:
                st.pop()
            ann = self._ann
            # a TraceMe belongs to the thread that opened it
            if ann is not None \
                    and getattr(_tls, "tid", None) == self.tid:
                if self.attrs:
                    ann.set_metadata(**self.attrs)
                ann.__exit__(None, None, None)

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self.end("error", error=str(exc))
        else:
            self.end("ok")
        return False


def start_span(name, parent=None, trace_id=None, parent_id=None,
               stack=True, loop=False, timed=False, **attrs):
    """Open a span.

    Parent resolution, in order: an explicit ``parent`` :class:`Span`
    (the cross-thread hand-off — an engine loop parents on the
    session's root span), an explicit wire context
    (``trace_id``/``parent_id`` from a KVStore message), else the
    calling thread's current span; with none of those this span ROOTS
    a fresh trace.  ``stack=False`` opts out of thread-local parenting
    for spans that outlive their opening call or end on another thread
    (a session's root span must not become the implicit parent of
    unrelated work on the submitting thread); such a span emits no
    annotation into a device profile, a stacked one does.
    ``loop=True`` says that this span, where it roots a trace, is one
    iteration of a loop: it and its descendants go to the loop ring (a
    span with a parent follows its parent).  Returns :data:`NULL_SPAN`
    when nothing records — unless ``timed``, which then returns a bare
    unrecorded span, for a caller that needs the length either way
    (``telemetry.phase``'s histogram)."""
    live = _profile_live()
    if not _enabled and not live:
        return Span(name, None, None, recorded=False) if timed \
            else NULL_SPAN
    return _open(name, parent, trace_id, parent_id, stack, loop, live,
                 attrs)


def setup_span(name, **attrs):
    """Open a stacked span that records **whether tracing is on or not**:
    set-up happens before anybody enables anything, and where a start's
    seconds went is asked afterwards (``setup.*`` in the benchmark, a
    flight-recorder dump, docs/observability.md "Set-up's account").  For
    work a process does a bounded number of times (an import, an engine's
    build, a program's first call, a ``bind``), NEVER in a loop: each one
    costs a recorded span (some 20 us) and a place in the ring.  Same
    :class:`Span`, same record and parenting as :func:`start_span`, an
    ``mx.`` annotation while a profile is live; finished, it lies in a
    ring of its own that :func:`setup_spans` reads (and :func:`tree`, and
    the flight recorder's dump), so that traffic traced later never
    pushes a start's account out, and :func:`spans_recent` stays what
    tracing recorded while it was on."""
    return _open(name, None, None, None, True, False, _profile_live(),
                 attrs, setup=True)


def _open(name, parent, trace_id, parent_id, stack, loop, live, attrs,
          setup=False):
    cur = _stack()
    if parent is not None and parent:
        tid, pid, loop = parent.trace_id, parent.span_id, parent._loop
    elif trace_id is not None:
        tid, pid, loop = trace_id, parent_id, False
    elif cur:
        top = cur[-1]
        tid, pid, loop = top.trace_id, top.span_id, top._loop
    else:
        tid, pid = _new_id(8), None
    sp = Span(name, tid, pid, loop=loop, setup=setup)
    sp.tid = _tls.tid
    if attrs:
        sp.attrs.update(attrs)
    with _lock:
        # bound the live set too: a span that is never ended (a bug,
        # or an abandoned session) must not leak forever — evict the
        # oldest as force-ended
        if len(_live) >= max(1024, _ring.maxlen):
            oldest = next(iter(_live.values()))
            _live.pop(oldest.span_id, None)
            oldest._ended = True
            oldest.status = "error"
            oldest.attrs["dropped"] = "live-ring-full"
            oldest._home().append(oldest)
        _live[sp.span_id] = sp
    if stack:
        sp._stacked = True
        cur.append(sp)
        if live:
            sp._ann = _Annotation("mx." + name)
            sp._ann.__enter__()
    return sp


def host_read(site):
    """The span of ONE blocking device-to-host read (``host_read``,
    attribute ``site``), wherever the program makes one outside a
    ``sync`` phase: ``with tracing.host_read("asnumpy"): ...``.  A
    reader counts the outermost of these and of the ``fit.sync`` spans
    on a thread."""
    return start_span("host_read", loop=True, site=site)


@contextlib.contextmanager
def frame(status="error"):
    """Ends, on the way out, every stacked span that the calling thread
    opened inside the block and left open: a loop body that raised in
    the middle of its iteration's span must not leave that span as the
    parent of whatever the thread does next."""
    st = _stack()
    depth = len(st)
    try:
        yield
    finally:
        while len(st) > depth:
            st.pop().end(status)


def current():
    """The calling thread's innermost live span, or None."""
    st = getattr(_tls, "stack", None)
    return st[-1] if st else None


def ctx():
    """The calling thread's current wire context (``{"trace_id",
    "span_id"}``), or None — what :meth:`KVStore._with_trace` stamps
    onto outgoing verbs.  One attr read when disabled/absent."""
    st = getattr(_tls, "stack", None)
    return st[-1].ctx() if st else None


def _trace_spans(trace_id):
    """Every recorded span of one trace: finished (from the rings) plus
    live (synthesized ``in_flight``), lock held by caller."""
    out = [sp._record() for ring in (_ring, _loop_ring, _setup_ring)
           for sp in ring if sp.trace_id == trace_id]
    out.extend(sp._record(live=True) for sp in _live.values()
               if sp.trace_id == trace_id)
    return out


def tree(trace_id):
    """Assemble one trace into a nested tree:

    ``{"trace_id", "n_spans", "root": {span..., "children": [...]},
    "extra_roots": [...], "orphans": [...], "complete": bool}``

    — ``orphans`` are spans whose parent is not in the trace (the
    chaos acceptance asserts this stays empty across a replica kill),
    ``extra_roots`` any parentless span beyond the first, and
    ``complete`` is True when a root exists, nothing is orphaned, and
    no span is still in flight.  Returns None for an unknown id."""
    with _lock:
        spans = _trace_spans(trace_id)
    if not spans:
        return None
    spans.sort(key=lambda s: s["t0_ns"])
    ids = {s["span_id"] for s in spans}
    children = {}
    roots, orphans = [], []
    for s in spans:
        pid = s["parent_id"]
        if pid is None:
            roots.append(s)
        elif pid in ids:
            children.setdefault(pid, []).append(s)
        else:
            orphans.append(s)

    def nest(s):
        return dict(s, children=[nest(c)
                                 for c in children.get(s["span_id"], [])])

    in_flight = any(s["status"] == "in_flight" for s in spans)
    return {"trace_id": trace_id, "n_spans": len(spans),
            "root": nest(roots[0]) if roots else None,
            "extra_roots": [nest(r) for r in roots[1:]],
            "orphans": [dict(s) for s in orphans],
            "complete": bool(roots) and not roots[1:] and not orphans
            and not in_flight}


def spans_recent(n=1000):
    """The newest ``n`` FINISHED spans of both rings (copies, in the
    order they ended) — what the flight recorder dumps as its ndjson
    span ring and the benchmark's readers take their records from."""
    with _lock:
        done = [sp for ring in (_ring, _loop_ring) for sp in ring]
    done.sort(key=lambda sp: sp.t1_ns or sp.t0_ns)
    return [sp._record() for sp in done[-int(n):]]


def setup_spans():
    """Every finished span that :func:`setup_span` opened (copies, in the
    order they ended; at most the ring's bound): a start's account, there
    with tracing off."""
    with _lock:
        done = list(_setup_ring)
    return [sp._record() for sp in done]


def reset():
    """Clear the finished rings and the live set (tests; enablement and
    other threads' stacks are unchanged)."""
    global _ring, _loop_ring, _setup_ring
    with _lock:
        _ring = deque(maxlen=_ring_size())
        _loop_ring = deque(maxlen=_ring_size())
        _setup_ring = deque(maxlen=_ring_size())
        _live.clear()
    st = getattr(_tls, "stack", None)
    if st:
        del st[:]
