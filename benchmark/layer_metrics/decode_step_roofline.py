"""The decode step's share of its roofline: the least time the chip could
take for one step, over the median device time of the step.  The least
time is the larger of bytes over the HBM rate and operations over the bf16
peak (``opcount/decode_engine.py``: every weight of the blocks and the head
once, K and V of the tokens that are LIVE, not of the rows the cache
reserves); for this tier it is the bytes that bind.  Live tokens are the
mean, over the window, of the tokens the running sessions held."""

import statistics

from benchmark.harness import find
from benchmark.opcount import decode_engine as opcount


def live_tokens(window):
    """Mean number of tokens resident over the window: each session's mean
    length while it generated, weighted by how long it did."""
    t0, t_end = window["t0"], window["t_end"]
    held = 0.0
    for r in window["requests"]:
        ts = [t for t in r.token_times if t0 <= t <= t_end]
        if len(ts) < 2:
            continue
        first = r.token_times.index(ts[0])
        mean_len = len(r.prompt) + first + len(ts) / 2.0
        held += mean_len * (ts[-1] - ts[0])
    return held / (t_end - t0)


def read(run):
    if run["peaks"] is None:
        return None
    steps = find("layer_metrics", "decode.step_device_ms").step_seconds(run)
    if not steps:
        return None
    median = statistics.median(steps)
    live = live_tokens(run["window"])
    slots = run["slots"]
    least = max(
        opcount.step_bytes(run["config"], live)
        / run["peaks"]["hbm_bytes_per_s"],
        opcount.step_flops(run["config"], slots, live)
        / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / median
