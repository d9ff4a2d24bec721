"""Median device time of one prefill program (``jit_prefill``) launched
between two decode steps: what an admission adds to the gap of every
running session."""

import statistics


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    pre = [d for name, _s, d in trace["devices"][0]["modules"]
           if name == "jit_prefill"]
    return 1e3 * statistics.median(pre) if pre else None
