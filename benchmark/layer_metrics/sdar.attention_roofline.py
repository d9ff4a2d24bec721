"""A pass's attention's share of its roofline in the SDAR cell: the least
time the chip could take for the rows the layers read in a pass (the larger
of K and V of ``rows_read`` rows over the HBM rate and, each of a block's
four rows and 32 heads over them, the scores' and sums' operations over the
bf16 peak: the cache is read once for four positions, so the operations are
a third of the bytes' time here), over the time a step spends in the
``decode_attention`` kernel at ``group`` 32 (its operation group in the
device trace).  None, and left out of the line, where the trace has no such
operation: the calls took the plain path."""

from benchmark.harness import find

KERNEL = "decode_attention"


def read(run):
    step = find("layer_metrics", "sdar.step_roofline").a_step(run)
    if step is None or run.get("peaks") is None:
        return None
    from benchmark.opcount import sdar_engine as opcount

    steps = find("layer_metrics", "decode.step_device_ms").step_seconds(run)
    spent = sum(s for g, s in run["trace"]["devices"][0][
        "op_seconds"].items() if KERNEL in g)
    if not steps or not spent:
        return None
    least = max(opcount.attention_bytes(run["config"], step["rows_read"])
                / run["peaks"]["hbm_bytes_per_s"],
                opcount.attention_flops(run["config"], step["rows_read"])
                / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / (spent / len(steps))
