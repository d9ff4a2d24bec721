"""Programs launched on the device per training step: the launches on the
first device in the traced window (the trace's ``XLA Modules`` line) over
the steps ``fit`` finished in it."""


def read(run):
    trace = run["trace"]
    if trace is None or not trace["counted"].get("steps"):
        return None
    return len(trace["devices"][0]["modules"]) / trace["counted"]["steps"]
