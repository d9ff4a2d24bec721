"""Share of the traced window in which no operation ran on the device, in a cell whose end-to-end metric is tokens per second:
``device.idle_pct.serve``'s reader (which moves ``itl_p95_ms`` in its own cells), under
the name that moves ``decode_tokens_per_s``."""

from benchmark.harness import find


def read(run):
    return find("layer_metrics", "device.idle_pct.serve").read(run)
