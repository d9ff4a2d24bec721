#!/usr/bin/env python3
"""What the program's spans cost a loop iteration, on the machine this runs
on: the decode engine's six spans of one step (iteration, queue, step,
dispatch, packed read, fan-out) opened and closed with nothing else
between them, best of five repeats, with recording off, on through
``MXNET_TRACE`` alone, and on under a live ``jax.profiler`` session with
the Python tracer off, as a traced benchmark run takes it.

    chiprun -- python3 benchmark/tools/span_cost.py
"""

import os
import shutil
import sys
import timeit

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def iteration(tracing):
    isp = tracing.start_span("serving.decode.iter", loop=True, replica="0")
    tracing.start_span("serving.decode.queue").end("ok", queued=1)
    with tracing.start_span("serving.decode.step") as ssp:
        with tracing.start_span("serving.decode.dispatch"):
            pass
        with tracing.host_read("decode.packed"):
            pass
        tracing.start_span("serving.decode.fanout").end("ok", emitted=12)
        ssp.annotate(live=12)
    isp.end("ok", admits=0, active=12)


def main():
    import jax

    from mxnet_tpu import tracing

    def best(n):
        return min(timeit.repeat(lambda: iteration(tracing), number=n,
                                 repeat=5)) / n * 1e6

    device = jax.devices()[0]
    print("device %s (%s); six spans of one engine iteration"
          % (device.platform, device.device_kind))
    tracing.disable()
    print("off:                     %8.2f us" % best(20000))
    tracing.enable()
    print("on (MXNET_TRACE):        %8.2f us" % best(5000))
    tracing.disable()
    directory = os.path.join(ROOT, ".bench_trace", "span_cost")
    shutil.rmtree(directory, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(directory, profiler_options=options)
    try:
        print("on (a profile is taken): %8.2f us" % best(2000))
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(directory, ignore_errors=True)
    tracing.reset()


if __name__ == "__main__":
    main()
