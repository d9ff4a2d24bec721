"""Cache rows a layer's attention reads for a live slot at a pass (its
length and its block of four), the mean over the traced seconds, from the
program's device counters (``rows_read`` over ``passes``): what the cache's
bytes and the attention's operations scale with."""

from benchmark.harness import find


def read(run):
    step = find("layer_metrics", "sdar.step_roofline").a_step(run)
    if step is None or not step["passes"]:
        return None
    return step["rows_read"] / step["passes"]
