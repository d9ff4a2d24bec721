"""The kernel ``flash_attention``'s share of its roofline in the DeepSeek-V2
cell over the traced seconds: the least time the chip could take for the
prompts' expanded attention of the prefills admitted in them (one call a
layer over the bucket each prompt falls in: 128 heads, scores over 192
values and weighted sums over 128, causal pairs; the larger of operations
over the bf16 peak and the heads' bytes over the HBM rate,
``opcount/deepseek_v2_engine.py``), over the time the device spent in the
kernel.  The operations bind.  The accepted ``flash_attention_roofline``
reads SmallThinker's family alone; this is the same reading for this one.
None, and left out of the line, where the trace has no such operation: no
prefill ran in the traced seconds, or its attention took the plain path."""

from benchmark.harness import find
from benchmark.opcount import deepseek_v2_engine as opcount

KERNEL = "flash_attention"


def read(run):
    trace = run["trace"]
    if trace is None or run["peaks"] is None \
            or run["config"].get("family") != "deepseek_v2_engine":
        return None
    spent = sum(s for g, s in trace["devices"][0]["op_seconds"].items()
                if KERNEL in g)
    config, peaks = run["config"], run["peaks"]
    total = int(config["num_hidden_layers"]) * sum(
        max(opcount.flash_flops(config, b) / peaks["bf16_flops_per_s"],
            opcount.flash_bytes(config, b) / peaks["hbm_bytes_per_s"])
        for b in find("layer_metrics", "smallthinker.prefill_roofline")
        .admitted_buckets(run))
    if not spent or not total:
        return None
    return 100.0 * total / spent
