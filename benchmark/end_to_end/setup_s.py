"""Seconds from the start of the process to the opening of the window:
imports, weights, compilation or cache loads, warm-up, the program's first
recorded steps or the traffic's ramp.  The reference's check runs after
the window and is not in it."""


def compute(run):
    return run["setup_s"]
