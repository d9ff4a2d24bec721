"""Blocking device-to-host reads the ``fit`` thread made per training step
in the traced seconds.  A read is a ``fit.sync`` phase or a ``host_read``
span of the program (``mxnet_tpu.tracing``; ``PERF.md`` section 3 lists
where each is opened); one inside another is one read, so the outermost
are counted, on the thread that ran the ``fit.batch`` spans.  The program
records its spans by itself while the device is profiled."""

from benchmark import trace_reduce

READS = ("fit.sync", "host_read")


def traced_spans(run):
    """The program's span records that ended inside the traced seconds
    (``[window.t_end - trace.window_s, window.t_end]`` on the monotonic
    clock, in nanoseconds), in the order they ended; [] in an untraced run
    and for a program whose spans are not on that clock."""
    trace = run["trace"]
    if trace is None:
        return []
    from mxnet_tpu import tracing

    hi = run["window"]["t_end"] * 1e9
    lo = hi - trace["window_s"] * 1e9
    return [r for r in tracing.spans_recent(1 << 20)
            if r.get("t1_ns") is not None and lo <= r["t1_ns"] <= hi]


def fit_thread(run):
    """(every traced span of the thread that ran the traced ``fit.batch``
    spans, that thread's merged read intervals), or None where there is no
    such span."""
    spans = traced_spans(run)
    tids = {r["tid"] for r in spans if r["name"] == "fit.batch"}
    if not tids:
        return None
    mine = [r for r in spans if r["tid"] in tids]
    reads = trace_reduce.union((r["t0_ns"], r["t1_ns"]) for r in mine
                               if r["name"] in READS)
    return mine, reads


def read(run):
    found = fit_thread(run)
    steps = run["trace"]["counted"].get("steps") if found else None
    if not steps:
        return None
    return len(found[1]) / steps
