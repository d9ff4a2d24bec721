"""Share of the positions fixed in the traced seconds that the confidence
threshold fixed (``low_confidence_dynamic``: every open position above it,
where those are at least the pass's quota), the rest by the quota's best
confidence (the program's device counters ``fixed_by_threshold`` and
``fixed_by_quota``).  Seeded weights give a nearly flat softmax, so this
reads 0: the reading that says what random weights do to the sampler."""

from benchmark.harness import find


def read(run):
    step = find("layer_metrics", "sdar.step_roofline").a_step(run)
    if step is None or not step["by_threshold"] + step["by_quota"]:
        return None
    return 100.0 * step["by_threshold"] \
        / (step["by_threshold"] + step["by_quota"])
