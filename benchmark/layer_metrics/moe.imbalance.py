"""The busiest held expert's picks over the mean held expert's, per sparse
layer, the worst layer, over the traced seconds (the program's routing
counters ``moe_picks[layer, expert]``): 1 is an even load."""


def read(run):
    trace = run["trace"]
    if trace is None or not trace["counted"].get("moe_steps"):
        return None
    picks = trace["counted"]["moe_picks"]
    if not picks.sum():
        return None
    return float(max(layer.max() / layer.mean() for layer in picks
                     if layer.sum()))
