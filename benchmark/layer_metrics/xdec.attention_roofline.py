"""The cross-decoder's attention's share of its roofline: K and V of the
rows the shared full layer holds for the live slots, times the layers that
read them (the full layer and every cross layer), over the HBM rate, over
the time a step spends in the ``decode_attention`` kernel (its operation
group in the device trace: the kernel's calls are the step's only custom
calls).  None, and left out of the line, where the trace has no such
operation: the calls took the plain path."""

from benchmark.harness import find
from benchmark.opcount import sambay_engine as opcount

KERNEL = "decode_attention"


def kernel_seconds(trace):
    """Seconds of the traced window in the decode-attention kernel: the
    operation groups that carry its name, or else the custom calls."""
    groups = trace["devices"][0]["op_seconds"]
    named = [s for g, s in groups.items() if KERNEL in g]
    if named:
        return sum(named)
    return sum(s for g, s in groups.items() if g.startswith("custom-call"))


def read(run):
    rows = find("layer_metrics", "sambay.step_roofline").rows_a_step(run)
    trace = run["trace"]
    if rows is None or run["peaks"] is None:
        return None
    steps = find("layer_metrics", "decode.step_device_ms").step_seconds(run)
    spent = kernel_seconds(trace)
    if not steps or not spent:
        return None
    config = run["config"]
    least = rows[1] * opcount.readers(config) * opcount.row_bytes(config) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (spent / len(steps))
