"""The decode step's attention's share of its roofline in the
Granite-4.0-H cell: K and V of the rows the four attention layers hold for
the live slots (``rows_full`` in each, from the program's counters) over
the HBM rate, over the time a step spends in the ``decode_attention``
kernel (its operation group in the device trace).  The first plain
grouped-query model on heads cached in pairs: a pair's eight queries read
the pair's rows once.  None, and left out of the line, where the trace has
no such operation: the calls took the plain path."""

from benchmark.harness import find

KERNEL = "decode_attention"


def read(run):
    rows = find("layer_metrics", "granite.step_roofline").rows_a_step(run)
    if rows is None or run.get("peaks") is None:
        return None
    from benchmark.opcount import granite_hybrid_engine as opcount

    steps = find("layer_metrics", "decode.step_device_ms").step_seconds(run)
    spent = sum(s for g, s in run["trace"]["devices"][0][
        "op_seconds"].items() if KERNEL in g)
    if not steps or not spent:
        return None
    least = max(
        opcount.step_state_bytes(run["config"], *rows)["full"]
        / run["peaks"]["hbm_bytes_per_s"],
        opcount.attention_flops(run["config"], rows[1])
        / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / (spent / len(steps))
