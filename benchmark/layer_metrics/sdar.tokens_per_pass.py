"""Tokens the commits delivered over live slot-passes, over the traced
seconds, from the program's device counters (``tokens_committed`` over
``passes``): what a pass is worth.  With one position fixed a pass, four
passes and a commit make a block of four: 0.8; a threshold that fixes
several positions a pass, or a commit merged into the next block's first
pass, raises it."""

from benchmark.harness import find


def read(run):
    step = find("layer_metrics", "sdar.step_roofline").a_step(run)
    if step is None or not step["passes"]:
        return None
    return step["tokens"] / step["passes"]
