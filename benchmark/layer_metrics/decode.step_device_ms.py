"""Median device time of one launch of the decode-step program
(``jit_step`` on the trace's ``XLA Modules`` line)."""

import statistics

STEP_MODULE = "jit_step"


def step_seconds(run):
    trace = run["trace"]
    if trace is None:
        return []
    return [d for name, _s, d in trace["devices"][0]["modules"]
            if name == STEP_MODULE]


def read(run):
    steps = step_seconds(run)
    return 1e3 * statistics.median(steps) if steps else None
