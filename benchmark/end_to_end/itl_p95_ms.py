"""95th percentile of the gaps between consecutive tokens of a session,
over all gaps that closed inside the window, client side (``on_token``
timestamps)."""

import statistics

from benchmark import stats


def gaps_ms(window):
    t0, t_end = window["t0"], window["t_end"]
    out = []
    for r in window["requests"]:
        ts = r.token_times
        out.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:])
                   if t0 <= b <= t_end)
    return out


def compute(run):
    gaps = gaps_ms(run["window"])
    p95 = stats.tail(gaps, 95.0)
    # the largest gaps beside the percentile: one stall of the whole server
    # costs tokens per second and leaves a 95th percentile where it was
    print("itl_ms: p95 %.3f median %.3f max %.3f over %d gaps, %d of them "
          "over 250 ms" % (p95, statistics.median(gaps), max(gaps),
                           len(gaps), sum(1 for g in gaps if g > 250.0)),
          flush=True)
    return p95
