"""The Granite-4.0-H decode step's share of its roofline: the least time
the chip could take for one step, over the median device time of the step
(``jit_step``).  The least time is the larger of bytes over the HBM rate
and operations over the bf16 peak (``opcount/granite_hybrid_engine.py``):
every weight once, the recurrent states and tails of the live slots read
and written, the K and V rows the attention layers hold for them, all from
the program's row counters over the traced seconds.  It is the bytes that
bind, and over half of them are recurrent state."""

import statistics

from benchmark.harness import find

FAMILY = "granite_hybrid_engine"


def rows_a_step(run):
    """``(rows, rows_full)`` a step over the traced seconds, by the
    program's device counters, or None where they counted nothing (a
    program without them, another family's cell, an untraced run)."""
    trace = run.get("trace")
    if trace is None or run["config"].get("family") != FAMILY:
        return None
    counted = trace.get("counted") or {}
    n = counted.get("ssd_steps")
    if not n or "rows" not in counted or "rows_full" not in counted:
        return None
    return counted["rows"] / float(n), counted["rows_full"] / float(n)


def read(run):
    rows = rows_a_step(run)
    if rows is None or run.get("peaks") is None:
        return None
    from benchmark.opcount import granite_hybrid_engine as opcount

    steps = find("layer_metrics", "decode.step_device_ms").step_seconds(run)
    if not steps:
        return None
    least = max(
        opcount.step_bytes(run["config"], *rows)
        / run["peaks"]["hbm_bytes_per_s"],
        opcount.step_flops(run["config"], *rows)
        / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / statistics.median(steps)
