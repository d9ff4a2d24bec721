"""The DeepSeek-V2 prefill's share of its roofline: the least time the chip
could take for one prefill, over the median device time of a prefill
(``jit_prefill``) in the traced seconds.  The least time of a bucket is the
larger of its operations over the bf16 peak and its bytes over the HBM rate
(``opcount/deepseek_v2_engine.py``: every layer over every position of the
bucket, K and V expanded from the latent rows, the 0.75 picks a row gives
this share, the expanded attention over causal pairs, every weight once);
the launches of the traced seconds are of several buckets, told apart by
nothing in the trace, so the least time is the median over the requests
whose first token came in those seconds of their bucket's.  None when the
traced seconds hold no admission."""

import statistics

from benchmark.harness import find
from benchmark.opcount import deepseek_v2_engine as opcount


def read(run):
    trace = run["trace"]
    if trace is None or run["peaks"] is None \
            or run["config"].get("family") != "deepseek_v2_engine":
        return None
    took = [d for name, _s, d in trace["devices"][0]["modules"]
            if name == "jit_prefill"]
    admitted = [max(opcount.prefill_flops(run["config"], b)
                    / run["peaks"]["bf16_flops_per_s"],
                    opcount.prefill_bytes(run["config"], b)
                    / run["peaks"]["hbm_bytes_per_s"])
                for b in find("layer_metrics", "smallthinker.prefill_roofline")
                .admitted_buckets(run)]
    if not took or not admitted:
        return None
    return 100.0 * statistics.median(admitted) / statistics.median(took)
