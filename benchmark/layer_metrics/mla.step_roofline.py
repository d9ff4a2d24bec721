"""The DeepSeek-V2 decode step's share of its roofline: the least time the
chip could take for one step, over the median device time of the step
(``jit_step``).  The least time is the larger of bytes over the HBM rate
and operations over the bf16 peak (``opcount/deepseek_v2_engine.py``):
every weight that takes part once, of the experts those that the routing
counters say were hit (an expert counts as hit in as many of the traced
steps as it got picks, at most all of them), and the latent rows the live
slots hold (``rows_latent`` x 1152 B), all from the program's device
counters over the traced seconds.  The latent rows' bytes and operations
meet on this chip; with the weights' bytes beside them the bytes bind."""

import statistics

from benchmark.harness import find
from benchmark.opcount import deepseek_v2_engine as opcount


def a_step(run):
    """What one step of the traced seconds did, by the program's device
    counters: ``rows``, ``rows_latent`` (over all layers), ``picks`` (of
    held experts) and ``experts_hit``; None where they counted nothing
    (another program's run has no such counters)."""
    trace = run["trace"]
    if trace is None or not trace["counted"].get("moe_steps") \
            or "rows_latent" not in trace["counted"]:
        return None
    counted = trace["counted"]
    n = float(counted["moe_steps"])
    picks = counted["moe_picks"]
    return {"rows": counted["moe_rows"] / n,
            "rows_latent": counted["rows_latent"] / n,
            "picks": float(picks.sum()) / n,
            "experts_hit": sum(min(1.0, p / n) for layer in picks
                               for p in layer)}


def read(run):
    step = a_step(run)
    if step is None or run["peaks"] is None:
        return None
    steps = find("layer_metrics", "decode.step_device_ms").step_seconds(run)
    if not steps:
        return None
    config = run["config"]
    least = max(
        opcount.step_bytes(config, step["experts_hit"], step["rows_latent"])
        / run["peaks"]["hbm_bytes_per_s"],
        opcount.step_flops(config, step["rows"], step["picks"],
                           step["rows_latent"])
        / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / statistics.median(steps)
