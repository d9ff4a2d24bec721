"""The kernel ``flash_attention``'s share of its roofline in the SDAR cell
over the traced seconds, under the mask that is causal by blocks: the least
time the chip could take for the prompts' attention of the prefills admitted
in them (one call a layer but the last, whose output nothing reads, over
the bucket each prompt falls in: 32 heads over 4 K/V heads of 128, a query
reading up to the end of its own block of four; the larger of operations
over the bf16 peak and the heads' bytes over the HBM rate,
``opcount/sdar_engine.py``), over the time the device spent in the kernel.
None, and left out of the line, where the trace has no such operation: no
prefill ran in the traced seconds, or its attention took the plain path."""

from benchmark.harness import find

KERNEL = "flash_attention"


def read(run):
    admitted = find("layer_metrics",
                    "sdar.prefill_roofline").admitted_buckets(run)
    if not admitted or run.get("peaks") is None:
        return None
    from benchmark.opcount import sdar_engine as opcount

    spent = sum(s for g, s in run["trace"]["devices"][0][
        "op_seconds"].items() if KERNEL in g)
    config, peaks = run["config"], run["peaks"]
    total = (int(config["num_hidden_layers"]) - 1) * sum(
        max(opcount.flash_flops(config, b) / peaks["bf16_flops_per_s"],
            opcount.flash_bytes(config, b) / peaks["hbm_bytes_per_s"])
        for b in admitted)
    if not spent or not total:
        return None
    return 100.0 * total / spent
