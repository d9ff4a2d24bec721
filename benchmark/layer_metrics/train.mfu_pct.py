"""Model FLOP/s utilization of training: the operations forward and
backward need per sample (``opcount/module_fit.py``: two per multiply-add
of every convolution and the classifier, no recomputation) times the
samples per second of this run's window, over the chips' bf16 peak
(``peaks.json``).  A model utilization, not a kernel's roofline share, and
it says nothing of idle time."""

from benchmark.opcount import module_fit as opcount


def read(run):
    w = run["window"]
    if "samples" not in w or run["peaks"] is None:
        return None
    rate = w["samples"] / (w["t_end"] - w["t0"])
    flops = opcount.train_flops_per_image(run["config"]) * rate
    return 100.0 * flops / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
