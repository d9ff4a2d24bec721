"""What the host adds to a decode step: the median length of an engine loop
iteration that admitted nobody (the program's ``serving.decode.iter``
spans with ``admits`` 0: lock and queue walk, dispatch, the packed read,
the fan-out of tokens) less the device time of the step it launched
(``decode.step_device_ms``'s own median).  With this and the step's device
time a token gap is accounted for."""

import statistics

from benchmark.harness import find


def read(run):
    spans = find("layer_metrics",
                 "fit.host_reads_per_step").traced_spans(run)
    iters = [r["dur_s"] for r in spans
             if r["name"] == "serving.decode.iter"
             and r["attrs"].get("admits") == 0]
    steps = find("layer_metrics", "decode.step_device_ms").step_seconds(run)
    if not iters or not steps:
        return None
    return 1e3 * (statistics.median(iters) - statistics.median(steps))
