"""Tokens a held expert sees in one decode step, over the traced seconds:
the picks the program's routing counters gave the held experts
(``moe_picks[layer, expert]``, kept on the device and read at both ends of
the trace) over held experts, sparse layers and steps.  The deployment the
cell stands for gives 16."""


def read(run):
    trace = run["trace"]
    if trace is None or not trace["counted"].get("moe_steps"):
        return None
    picks = trace["counted"]["moe_picks"]
    return float(picks.sum()) / (picks.size * trace["counted"]["moe_steps"])
