"""The K-EXAONE decode step's share of its roofline: the least time the
chip could take for one step, over the median device time of the step
(``jit_step``).  The least time is the larger of bytes over the HBM rate
and operations over the bf16 peak (``opcount/exaone_moe_engine.py``): every
weight that takes part once, of the routed experts those that the routing
counters say were hit (a held expert counts as hit in as many of the
traced steps as it got picks, at most all of them), K and V of the tokens
live in the traced seconds, a window layer's capped at the window a
session.  It is the bytes that bind."""

import statistics

from benchmark.harness import find
from benchmark.opcount import exaone_moe_engine as opcount


def live_tokens(run, cap):
    """Mean, over the traced seconds, of the tokens the running sessions
    held, each session's count capped at ``cap``."""
    t_end = run["window"]["t_end"]
    t0 = t_end - run["trace"]["window_s"]
    held = 0.0
    for r in run["window"]["requests"]:
        first = None
        for i, t in enumerate(r.token_times):
            if t0 <= t <= t_end:
                if first is None:
                    first, t_prev = i, t
                    continue
                held += min(cap, len(r.prompt) + i) * (t - t_prev)
                t_prev = t
    return held / (t_end - t0)


def read(run):
    trace = run["trace"]
    if trace is None or run["peaks"] is None:
        return None
    counted = trace["counted"]
    steps = find("layer_metrics", "decode.step_device_ms").step_seconds(run)
    if not steps or not counted.get("moe_steps"):
        return None
    config = run["config"]
    n = float(counted["moe_steps"])
    picks = counted["moe_picks"]
    experts_hit = sum(min(1.0, p / n) for layer in picks for p in layer)
    live_full = live_tokens(run, 1 << 30)
    live_window = live_tokens(run, int(config["sliding_window"]))
    least = max(
        opcount.step_bytes(config, experts_hit, live_full, live_window)
        / run["peaks"]["hbm_bytes_per_s"],
        opcount.step_flops(config, counted["moe_rows"] / n,
                           float(picks.sum()) / n, live_full, live_window)
        / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / statistics.median(steps)
