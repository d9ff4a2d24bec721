"""Rows the shared full layer holds for a live slot at a step, the mean
over the traced seconds, from the program's device counters (``rows_full``
over ``rows``): what the eight reads of that layer, four tenths of the
step's bytes, scale with."""

from benchmark.harness import find


def read(run):
    rows = find("layer_metrics", "sambay.step_roofline").rows_a_step(run)
    if rows is None or not rows[0]:
        return None
    return rows[1] / rows[0]
