"""Rows an attention layer holds for a live slot at a step, the mean over
the traced seconds, from the program's device counters (``rows_full`` over
``rows``): what the four attention layers' bytes scale with, while the
recurrent state's do not."""

from benchmark.harness import find


def read(run):
    rows = find("layer_metrics", "granite.step_roofline").rows_a_step(run)
    if rows is None or not rows[0]:
        return None
    return rows[1] / rows[0]
