"""The SDAR prefill's share of its roofline: the least time the chip could
take for one prefill, over the median device time of a prefill
(``jit_prefill``) in the traced seconds.  The least time of a bucket is the
larger of its operations over the bf16 peak and its bytes over the HBM rate
(``opcount/sdar_engine.py``: every layer but the last over every position
of the bucket with eight experts a row, attention under the mask that is
causal by blocks, of the last layer its K and V, no head, every weight
once); the launches of the traced seconds are of several buckets, told
apart by nothing in the trace, so the least time is the median over the
requests admitted in those seconds of their bucket's.  A request counts as
admitted there when its first block's commit (its first tokens) came in
them: a prefill yields no token, and the commit follows it by five passes.
None when the traced seconds hold no admission."""

import statistics


def admitted_buckets(run):
    """The bucket of each request whose first tokens came in the traced
    seconds."""
    trace = run.get("trace")
    if trace is None or run["config"].get("family") != "sdar_engine":
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
    from benchmark.opcount import sdar_engine as opcount

    took = [d for name, _s, d in run["trace"]["devices"][0]["modules"]
            if name == "jit_prefill"]
    if not took:
        return None
    least = [max(opcount.prefill_flops(run["config"], b)
                 / run["peaks"]["bf16_flops_per_s"],
                 opcount.prefill_bytes(run["config"], b)
                 / run["peaks"]["hbm_bytes_per_s"]) for b in admitted]
    return 100.0 * statistics.median(least) / statistics.median(took)
