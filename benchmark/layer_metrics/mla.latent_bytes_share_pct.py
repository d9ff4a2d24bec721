"""What latent attention is of a step's needed bytes: the latent rows the
live slots hold and attention's own projections (both halves of
``kv_b_proj`` among them), over every byte the step has to read
(``opcount/deepseek_v2_engine.py``, from the program's device counters over
the traced seconds)."""

from benchmark.harness import find
from benchmark.opcount import deepseek_v2_engine as opcount


def read(run):
    step = find("layer_metrics", "mla.step_roofline").a_step(run)
    if step is None:
        return None
    config = run["config"]
    return 100.0 * (opcount.latent_bytes(config, step["rows_latent"])
                    + opcount.attention_weight_bytes(config)) \
        / opcount.step_bytes(config, step["experts_hit"],
                             step["rows_latent"])
