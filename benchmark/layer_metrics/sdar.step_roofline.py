"""The SDAR pass's share of its roofline: the least time the chip could
take for one pass (the decode step of a model that generates by blocks),
over the median device time of the step (``jit_step``).  The least time is
the larger of bytes over the HBM rate and operations over the bf16 peak
(``opcount/sdar_engine.py``): every weight that takes part once, of the
experts those that the routing counters say were hit (an expert counts as
hit in as many of the traced steps as it got picks, at most all of them), K
and V of the rows the live slots' blocks read (``rows_read``, once a block
of four rows), all from the program's device counters over the traced
seconds.  With 384 rows over 128 held experts the two lie close; the bytes
bind."""

import statistics

from benchmark.harness import find


def a_step(run):
    """What one pass of the traced seconds did, by the program's device
    counters: ``rows``, ``rows_read``, ``picks`` (of held experts),
    ``experts_hit``, and the sampler's ``passes`` (live slot-passes),
    ``commits``, ``tokens``, ``by_threshold`` and ``by_quota``; None where
    they counted nothing (another program's run has no such counters)."""
    trace = run.get("trace")
    counted = (trace or {}).get("counted") or {}
    if not counted.get("moe_steps") or "sdar_passes" not in counted \
            or "moe_picks" not in counted:
        return None
    n = float(counted["moe_steps"])
    picks = counted["moe_picks"]
    return {"rows": counted.get("moe_rows", 0) / n,
            "rows_read": counted.get("sdar_rows_read", 0) / n,
            "picks": float(picks.sum()) / n,
            "experts_hit": sum(min(1.0, p / n) for layer in picks
                               for p in layer),
            "passes": counted["sdar_passes"] / n,
            "commits": counted.get("sdar_commits", 0) / n,
            "tokens": counted.get("sdar_tokens_committed", 0) / n,
            "by_threshold": counted.get("sdar_fixed_by_threshold", 0) / n,
            "by_quota": counted.get("sdar_fixed_by_quota", 0) / n}


def read(run):
    step = a_step(run)
    if step is None or run.get("peaks") is None:
        return None
    from benchmark.opcount import sdar_engine as opcount

    steps = find("layer_metrics", "decode.step_device_ms").step_seconds(run)
    if not steps:
        return None
    config = run["config"]
    least = max(
        opcount.step_bytes(config, step["experts_hit"], step["rows_read"])
        / run["peaks"]["hbm_bytes_per_s"],
        opcount.step_flops(config, step["rows"], step["picks"],
                           step["rows_read"])
        / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / statistics.median(steps)
