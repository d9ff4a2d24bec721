"""The kernel ``flash_attention``'s share of its roofline over the traced
seconds: the least time the chip could take for the prompts' attention of
the prefills admitted in them (one call a layer over the bucket each prompt
falls in, a window layer's counted with its window; the larger of
operations over the bf16 peak and bytes over the HBM rate,
``opcount/smallthinker_engine.py``), over the time the device spent in the
kernel.  The operations bind.  None, and left out of the line, where the
trace has no such operation: no prefill ran in the traced seconds, or its
attention took the plain path."""

from benchmark.harness import find
from benchmark.opcount import smallthinker_engine as opcount

KERNEL = "flash_attention"


def read(run):
    trace = run["trace"]
    if trace is None or run["peaks"] is None \
            or run["config"].get("family") != "smallthinker_engine":
        return None
    spent = sum(s for g, s in trace["devices"][0]["op_seconds"].items()
                if KERNEL in g)
    config = run["config"]
    full, rings = opcount.kinds(config)
    window = int(config["sliding_window_size"])
    peaks = run["peaks"]

    def least(bucket, reach):
        return max(opcount.flash_flops(config, bucket, reach)
                   / peaks["bf16_flops_per_s"],
                   opcount.flash_bytes(config, bucket)
                   / peaks["hbm_bytes_per_s"])

    total = sum(full * least(b, None) + rings * least(b, window)
                for b in find("layer_metrics", "smallthinker.prefill_roofline")
                .admitted_buckets(run))
    if not spent or not total:
        return None
    return 100.0 * total / spent
