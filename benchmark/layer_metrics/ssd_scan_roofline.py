"""The kernel ``ssd_scan``'s share of its roofline over the traced seconds:
the least time the chip could take for the chunked scans of the prefills
admitted in them (the larger of operations over the bf16 peak and bytes
over the HBM rate, ``opcount/granite_hybrid_engine.py``, one call a mamba
layer over the bucket each prompt falls in), over the time the device
spent in the kernel.  The bytes bind the least time (``x`` in and ``y`` out
in float32); the kernel's own time is its matrix products and the ``(Q,
Q)`` decays it makes for each head.  None, and left out of the line, where
the trace has no such operation: the scans took the plain path."""

from benchmark.harness import find

KERNEL = "ssd_scan"


def read(run):
    admitted = find("layer_metrics",
                    "granite.prefill_roofline").admitted_buckets(run)
    if not admitted or run.get("peaks") is None:
        return None
    from benchmark.opcount import granite_hybrid_engine as opcount

    config = run["config"]
    spent = sum(s for g, s in run["trace"]["devices"][0][
        "op_seconds"].items() if KERNEL in g)
    if not spent:
        return None
    layers = opcount.kinds(config)["mamba"]
    least = sum(
        layers * max(opcount.scan_flops(config, b)
                     / run["peaks"]["bf16_flops_per_s"],
                     opcount.scan_bytes(config, b)
                     / run["peaks"]["hbm_bytes_per_s"]) for b in admitted)
    return 100.0 * least / spent
