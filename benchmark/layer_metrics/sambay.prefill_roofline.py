"""The SambaY prefill's share of its roofline: the least time the chip
could take for one prefill, over the median device time of a prefill
(``jit_prefill``) in the traced seconds.  The least time of a bucket is the
larger of its operations over the bf16 peak and its bytes over the HBM rate
(``opcount/sambay_engine.py``: the layers up to the full one over every
position of the bucket, the layers after it for one row, every weight
once); the launches of the traced seconds are of several buckets, told
apart by nothing in the trace, so the least time is the median over the
requests whose first token came in those seconds of their bucket's."""

import statistics

from benchmark.opcount import sambay_engine as opcount


def read(run):
    trace = run["trace"]
    if trace is None or run["peaks"] is None \
            or run["config"].get("family") != "sambay_engine":
        return None
    took = [d for name, _s, d in trace["devices"][0]["modules"]
            if name == "jit_prefill"]
    t_end = run["window"]["t_end"]
    t0 = t_end - trace["window_s"]
    buckets = sorted(run["config"]["engine"]["prefill_buckets"])
    least = {b: max(opcount.prefill_flops(run["config"], b)
                    / run["peaks"]["bf16_flops_per_s"],
                    opcount.prefill_bytes(run["config"], b)
                    / run["peaks"]["hbm_bytes_per_s"]) for b in buckets}
    admitted = [least[next(b for b in buckets if len(r.prompt) <= b)]
                for r in run["window"]["requests"]
                if r.token_times and t0 <= r.token_times[0] <= t_end]
    if not took or not admitted:
        return None
    return 100.0 * statistics.median(admitted) / statistics.median(took)
