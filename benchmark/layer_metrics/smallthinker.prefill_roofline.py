"""The SmallThinker prefill's share of its roofline: the least time the
chip could take for one prefill, over the median device time of a prefill
(``jit_prefill``) in the traced seconds.  The least time of a bucket is the
larger of its operations over the bf16 peak and its bytes over the HBM rate
(``opcount/smallthinker_engine.py``: every layer over every position of
the bucket with six experts a row, attention counted with its window, every
weight once); the launches of the traced seconds are of several buckets,
told apart by nothing in the trace, so the least time is the median over
the requests whose first token came in those seconds of their bucket's.
None when the traced seconds hold no admission."""

import statistics

from benchmark.opcount import smallthinker_engine as opcount


def admitted_buckets(run):
    """The bucket of each request whose first token came in the traced
    seconds."""
    t_end = run["window"]["t_end"]
    t0 = t_end - run["trace"]["window_s"]
    buckets = sorted(run["config"]["engine"]["prefill_buckets"])
    return [next(b for b in buckets if len(r.prompt) <= b)
            for r in run["window"]["requests"]
            if r.token_times and t0 <= r.token_times[0] <= t_end]


def read(run):
    trace = run["trace"]
    if trace is None or run["peaks"] is None \
            or run["config"].get("family") != "smallthinker_engine":
        return None
    took = [d for name, _s, d in trace["devices"][0]["modules"]
            if name == "jit_prefill"]
    admitted = [max(opcount.prefill_flops(run["config"], b)
                    / run["peaks"]["bf16_flops_per_s"],
                    opcount.prefill_bytes(run["config"], b)
                    / run["peaks"]["hbm_bytes_per_s"])
                for b in admitted_buckets(run)]
    if not took or not admitted:
        return None
    return 100.0 * statistics.median(admitted) / statistics.median(took)
