"""The decode step's attention's share of its roofline in the SmallThinker
cell: K and V of the rows the layers read in a step (``rows_full`` in each
full layer, ``rows_ring`` in each ring) over the HBM rate, over the time a
step spends in the ``decode_attention`` kernel (its operation group in the
device trace; full layers and rings go through the one kernel).  None, and
left out of the line, where the trace has no such operation: the calls took
the plain path."""

from benchmark.harness import find
from benchmark.opcount import smallthinker_engine as opcount

KERNEL = "decode_attention"


def read(run):
    step = find("layer_metrics", "smallthinker.step_roofline").a_step(run)
    if step is None or run["peaks"] is None:
        return None
    steps = find("layer_metrics", "decode.step_device_ms").step_seconds(run)
    spent = sum(s for g, s in run["trace"]["devices"][0][
        "op_seconds"].items() if KERNEL in g)
    if not steps or not spent:
        return None
    least = opcount.attention_bytes(run["config"], step["rows_full"],
                                    step["rows_ring"]) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (spent / len(steps))
