"""What the window keeps of the rows a full layer would read: the rows the
rings hold for the live slots over the rows a full layer holds for them
(``rows_ring`` over ``rows_full``, the program's device counters over the
traced seconds).  100 while no session is past the window."""

from benchmark.harness import find


def read(run):
    step = find("layer_metrics", "smallthinker.step_roofline").a_step(run)
    if step is None or not step["rows_full"]:
        return None
    return 100.0 * step["rows_ring"] / step["rows_full"]
