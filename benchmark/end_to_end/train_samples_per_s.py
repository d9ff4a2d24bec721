"""Global samples trained per second of the window, from the device
barrier that opens it to the device barrier that closes it: all the steps
over all the time."""


def compute(run):
    w = run["window"]
    if "samples" not in w:
        return None
    return w["samples"] / (w["t_end"] - w["t0"])
