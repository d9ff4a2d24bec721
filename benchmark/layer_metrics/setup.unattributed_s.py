"""What is left of set-up once everything the program can name is taken
off: ``setup_s`` less the traffic's ramp, less the union of the interval
before the program's import, every trace, lowering and pass through
``compile_or_get_cached`` the program heard (``compile_cache.phases()``)
and every span of set-up (``tracing.setup_spans()``: the import, an engine's
build and each program's first call, a pool's start, a module's ``bind``,
``init_params`` and ``init_optimizer``, the manifest's ``note_build``).
What stays are the benchmark's own calls between those: the family's
weights drawn on the device (less the loads that takes), its first short
session or recorded first steps, the harness.

:func:`account` is the one reading all six ``setup.*`` metrics take their
numbers from.  Every second before the ramp is counted once: an instant
inside a load is the load's, else inside a lowering the lowering's, else
inside a trace the trace's, else inside a span the spans', so

    setup_s = before_program + trace + lower + load + spans + ramp
              + unattributed

to the last digit.  ``load`` is ``exec.setup_load_s``'s seconds wherever no
two loads overlap and none ends in the ramp."""

from benchmark import trace_reduce

NOTE_BUILD = "compile_cache.note_build"
IMPORT = "setup.import"


def _clip(intervals, lo, hi):
    """Merged (start, end) pairs of ``intervals`` inside ``[lo, hi]``."""
    return trace_reduce.union(
        (max(a, lo), min(b, hi)) for a, b in intervals
        if min(b, hi) > max(a, lo))


def _minus(mine, taken):
    """What the merged ``mine`` keeps outside the merged ``taken``."""
    out = []
    for a, b in mine:
        for c, d in taken:
            if d <= a or c >= b:
                continue
            if c > a:
                out.append([a, c])
            a = max(a, d)
            if a >= b:
                break
        if a < b:
            out.append([a, b])
    return out


def _seconds(merged):
    return sum(b - a for a, b in merged)


def account(run):
    """The parts of set-up, in seconds, or None in an untraced run and for
    a program that keeps no such records (the parent commit)."""
    if run["trace"] is None:
        return None
    from mxnet_tpu import compile_cache, tracing

    phases = getattr(compile_cache, "phases", None)
    spans = getattr(tracing, "setup_spans", None)
    if phases is None or spans is None:
        return None
    ramp = float(run["traffic"].get("ramp_seconds", 0.0))
    t0 = run["window"]["t0"]
    return parts_of(phases(), [
        (r["name"], r["t0_ns"] * 1e-9, r["t1_ns"] * 1e-9, r["tid"])
        for r in spans()], t0 - run["setup_s"], t0 - ramp, ramp)


def parts_of(phases, spans, start, cut, ramp):
    """The account of ``[start, cut]`` from ``phases`` (as
    ``compile_cache.phases()`` gives them) and ``spans`` ((name, t0, t1,
    tid) in seconds), or None where no ``setup.import`` span began in it."""
    spans = [s for s in spans if s[1] < cut]
    heard = [p for p in phases if p[2] < cut]
    imported = [a for name, a, _b, _tid in spans if name == IMPORT]
    if not imported:
        return None
    before = _clip([(start, min(imported))], start, cut)

    def of(*names):
        return _clip([(a, b) for phase, _fn, a, b, _tid in heard
                      if phase in names], start, cut)

    load = of("load", "compile")
    lower = _minus(of("lower"), load)
    trace = _minus(of("trace"), trace_reduce.union(load + lower))
    named = trace_reduce.union(before + load + lower + trace)
    own = _minus(_clip([(a, b) for _n, a, b, _tid in spans], start, cut),
                 named)
    # traced and lowered again for the manifest's fingerprint: on the
    # thread that ran the note_build span, inside it
    again = []
    for name, a, b, tid in spans:
        if name == NOTE_BUILD:
            again += [(max(a, c), min(b, d))
                      for phase, _fn, c, d, ptid in heard
                      if phase in ("trace", "lower") and ptid == tid
                      and min(b, d) > max(a, c)]
    again = _minus(_clip(again, start, cut), load)
    lowerings = sum(1 for p in heard if p[0] == "lower" and p[3] <= cut)
    programs = sum(1 for p in heard
                   if p[0] in ("load", "compile") and p[3] <= cut)
    parts = {"before_program_s": _seconds(before),
             "trace_s": _seconds(trace), "lower_s": _seconds(lower),
             "load_s": _seconds(load), "spans_s": _seconds(own),
             "relower_s": _seconds(again), "ramp_s": ramp,
             "lowerings": lowerings, "programs": programs}
    parts["unattributed_s"] = (cut - start) - _seconds(
        trace_reduce.union(named + own))
    return parts


def read(run):
    parts = account(run)
    return None if parts is None else parts["unattributed_s"]
