"""The SambaY decode step's share of its roofline: the least time the chip
could take for one step, over the median device time of the step
(``jit_step``).  The least time is the larger of bytes over the HBM rate
and operations over the bf16 peak (``opcount/sambay_engine.py``): every
weight once, the recurrent states and tails of the live slots read and
written, the rows the rings hold for them, and the rows the shared full
layer holds for them times its eight readers, all from the program's row
counters over the traced seconds.  It is the bytes that bind."""

import statistics

from benchmark.harness import find
from benchmark.opcount import sambay_engine as opcount


def rows_a_step(run):
    """``(rows, rows_full, rows_ring)`` a step over the traced seconds, by
    the program's device counters, or None where they counted nothing."""
    trace = run["trace"]
    if trace is None or not trace["counted"].get("ssm_steps"):
        return None
    counted = trace["counted"]
    n = float(counted["ssm_steps"])
    return tuple(counted[k] / n for k in ("rows", "rows_full", "rows_ring"))


def read(run):
    rows = rows_a_step(run)
    if rows is None or run["peaks"] is None:
        return None
    steps = find("layer_metrics", "decode.step_device_ms").step_seconds(run)
    if not steps:
        return None
    least = max(
        opcount.step_bytes(run["config"], *rows)
        / run["peaks"]["hbm_bytes_per_s"],
        opcount.step_flops(run["config"], *rows)
        / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / statistics.median(steps)
