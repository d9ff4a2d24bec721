"""Median length of one admission on the engine's thread (the program's
``serving.admit`` span: padding, the prefill dispatch, the first-token
read, the emit and the bookkeeping): what an admission adds to the gap of
every running session, of which ``decode.prefill_stall_ms`` is the device's
part."""

import statistics

from benchmark.harness import find


def read(run):
    spans = find("layer_metrics",
                 "fit.host_reads_per_step").traced_spans(run)
    admits = [r["dur_s"] for r in spans if r["name"] == "serving.admit"]
    return 1e3 * statistics.median(admits) if admits else None
