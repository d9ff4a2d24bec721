"""The absorbed step's attention's share of its roofline: the least time
the chip could take for the latent rows the layers read in a step (the
larger of their bytes, 1152 B a row, over the HBM rate and their
operations, 128 heads x (576 + 512) multiply-adds a row, over the bf16
peak: the two meet on a v5e), over the time a step spends in the
``latent_attention`` kernel (its operation group in the device trace).
None, and left out of the line, where the trace has no such operation: the
calls took the plain path."""

from benchmark.harness import find
from benchmark.opcount import deepseek_v2_engine as opcount

KERNEL = "latent_attention"


def read(run):
    step = find("layer_metrics", "mla.step_roofline").a_step(run)
    if step is None or run["peaks"] is None:
        return None
    steps = find("layer_metrics", "decode.step_device_ms").step_seconds(run)
    spent = sum(s for g, s in run["trace"]["devices"][0][
        "op_seconds"].items() if KERNEL in g)
    if not steps or not spent:
        return None
    least = max(opcount.latent_bytes(run["config"], step["rows_latent"])
                / run["peaks"]["hbm_bytes_per_s"],
                opcount.latent_flops(run["config"], step["rows_latent"])
                / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / (spent / len(steps))
