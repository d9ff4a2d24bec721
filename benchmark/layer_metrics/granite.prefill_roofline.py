"""The Granite-4.0-H prefill's share of its roofline: the least time the
chip could take for one prefill, over the median device time of a prefill
(``jit_prefill``) in the traced seconds.  The least time of a bucket is the
larger of its operations over the bf16 peak and its bytes over the HBM rate
(``opcount/granite_hybrid_engine.py``: every layer over every position of
the bucket, the 36 chunked scans, the head for one row, every weight once);
the launches of the traced seconds are of several buckets, told apart by
nothing in the trace, so the least time is the median over the requests
whose first token came in those seconds of their bucket's."""

import statistics

FAMILY = "granite_hybrid_engine"


def admitted_buckets(run):
    """The bucket of each request whose first token came in the traced
    seconds."""
    trace = run.get("trace")
    if trace is None or run["config"].get("family") != FAMILY:
        return []
    t_end = run["window"]["t_end"]
    t0 = t_end - trace["window_s"]
    buckets = sorted(run["config"]["engine"]["prefill_buckets"])
    return [next(b for b in buckets if len(r.prompt) <= b)
            for r in run["window"]["requests"]
            if r.token_times and t0 <= r.token_times[0] <= t_end]


def read(run):
    admitted = admitted_buckets(run)
    if not admitted or run.get("peaks") is None:
        return None
    from benchmark.opcount import granite_hybrid_engine as opcount

    took = [d for name, _s, d in run["trace"]["devices"][0]["modules"]
            if name == "jit_prefill"]
    if not took:
        return None
    least = [max(opcount.prefill_flops(run["config"], b)
                 / run["peaks"]["bf16_flops_per_s"],
                 opcount.prefill_bytes(run["config"], b)
                 / run["peaks"]["hbm_bytes_per_s"]) for b in admitted]
    return 100.0 * statistics.median(least) / statistics.median(took)
