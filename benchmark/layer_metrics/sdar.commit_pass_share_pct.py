"""Share of the live slot-passes that were commits, over the traced seconds
(the program's device counters ``commits`` over ``passes``): the passes that
fix nothing and only keep the final K and V, one in five with one position
a pass.  What a commit merged into the next block's first pass would take
off."""

from benchmark.harness import find


def read(run):
    step = find("layer_metrics", "sdar.step_roofline").a_step(run)
    if step is None or not step["passes"]:
        return None
    return 100.0 * step["commits"] / step["passes"]
