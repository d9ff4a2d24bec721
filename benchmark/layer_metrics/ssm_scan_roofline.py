"""The kernel ``ssm_scan``'s share of its roofline over the traced seconds:
the least time the chip could take for the recurrences of the prefills
admitted in them (the larger of operations over the bf16 peak and bytes
over the HBM rate, ``opcount/sambay_engine.py``, one call a state-space
layer over the bucket each prompt falls in), over the time the device
spent in the kernel.  The bytes bind the least time; the kernel itself is
bound by neither: a position's exponential waits for the state the
position before it left.  None, and left out of the line, where the trace
has no such operation: the scans took the plain path."""

from benchmark.opcount import sambay_engine as opcount

KERNEL = "ssm_scan"


def read(run):
    trace = run["trace"]
    if trace is None or run["peaks"] is None \
            or run["config"].get("family") != "sambay_engine":
        return None
    spent = sum(s for g, s in trace["devices"][0]["op_seconds"].items()
                if KERNEL in g)
    t_end = run["window"]["t_end"]
    t0 = t_end - trace["window_s"]
    config = run["config"]
    buckets = sorted(config["engine"]["prefill_buckets"])
    layers = opcount.kinds(config)["mamba"]
    least = sum(
        layers * max(opcount.scan_flops(config, b)
                     / run["peaks"]["bf16_flops_per_s"],
                     opcount.scan_bytes(config, b)
                     / run["peaks"]["hbm_bytes_per_s"])
        for b in (next(b for b in buckets if len(r.prompt) <= b)
                  for r in run["window"]["requests"]
                  if r.token_times and t0 <= r.token_times[0] <= t_end))
    if not spent or not least:
        return None
    return 100.0 * least / spent
