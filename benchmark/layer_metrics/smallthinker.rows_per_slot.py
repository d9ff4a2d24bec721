"""Rows a full layer holds for a live slot at a step, the mean over the
traced seconds, from the program's device counters (``rows_full`` over
``rows``): what the full layers' bytes scale with, and how far past the
window's 4096 the sessions are."""

from benchmark.harness import find


def read(run):
    step = find("layer_metrics", "smallthinker.step_roofline").a_step(run)
    if step is None or not step["rows"]:
        return None
    return step["rows_full"] / step["rows"]
