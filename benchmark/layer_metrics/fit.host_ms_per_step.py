"""What the host itself spends on a training step: a ``fit.batch`` span and
the ``fit.data`` wait before it, less the blocking device-to-host reads
inside them (``fit.sync`` and ``host_read`` spans,
``fit.host_reads_per_step`` has them), the lower quartile over the batches
of the traced seconds.

Not the mean: a runtime lets the host run only so many launches ahead of
the device, and once the thread is that far ahead each step is held inside
a dispatch for as long as the device takes (PR 24 saw 11 steps of 16-19 ms
after a read had drained the queue, then steps of 177-179 ms, the device's
own).  That wait is in no read's span, and traced seconds over steps then
reads the device's step, not the host's.  More than a quarter of any
stretch of steps runs free (the host gets ahead again after every read),
so the lower quartile is a step the runtime did not hold: the time under
which the device's step cannot fall without the host becoming the
bottleneck."""

import statistics

from benchmark.harness import find


def _inside(intervals, start, end):
    return sum(min(e, end) - max(s, start) for s, e in intervals
               if s < end and e > start)


def read(run):
    found = find("layer_metrics", "fit.host_reads_per_step").fit_thread(run)
    if found is None:
        return None
    mine, reads = found
    own, start = [], None
    for r in sorted((r for r in mine
                     if r["name"] in ("fit.data", "fit.batch")),
                    key=lambda r: r["t0_ns"]):
        if r["name"] == "fit.data":     # the wait before the next batch
            start = r["t0_ns"]
            continue
        start = r["t0_ns"] if start is None else start
        own.append((r["t1_ns"] - start
                    - _inside(reads, start, r["t1_ns"])) * 1e-6)
        start = None
    if len(own) < 2:
        return own[0]
    return statistics.quantiles(own, n=4)[0]
