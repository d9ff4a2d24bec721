"""Latent rows a layer holds for a live slot at a step, the mean over the
traced seconds, from the program's device counters (``rows_latent`` over
``rows`` over the layers): what the latent rows' bytes and the absorbed
attention's operations scale with."""

from benchmark.harness import find


def read(run):
    step = find("layer_metrics", "mla.step_roofline").a_step(run)
    if step is None or not step["rows"]:
        return None
    return step["rows_latent"] / step["rows"] \
        / int(run["config"]["num_hidden_layers"])
