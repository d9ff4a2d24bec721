"""Share of the traced window in which no operation ran on the device, the
worst device where there are several: 100 x (1 - busy / window), busy being
the union of the device-operation intervals in the profiler's trace.  The
serving cells' reading (``trace_reduce.idle_pct``)."""

from benchmark import trace_reduce


def read(run):
    if run["trace"] is None or "requests" not in run["window"]:
        return None
    return trace_reduce.idle_pct(run["trace"])
