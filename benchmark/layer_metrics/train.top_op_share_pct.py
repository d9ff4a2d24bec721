"""Share of the device's busy time spent in its five longest groups of
operations (the names are in the run's ``breakdown``): how concentrated the
step is, and so how much one kernel can move it."""


def read(run):
    trace = run["trace"]
    if trace is None or "samples" not in run["window"]:
        return None
    d = trace["devices"][0]
    top = sorted(d["op_seconds"].values(), reverse=True)[:5]
    return 100.0 * sum(top) / sum(d["op_seconds"].values())
