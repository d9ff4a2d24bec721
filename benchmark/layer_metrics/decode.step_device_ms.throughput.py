"""Median device time of one launch of the decode-step program, in a cell whose end-to-end metric is tokens per second:
``decode.step_device_ms``'s reader (which moves ``itl_p95_ms`` in its own cells), under
the name that moves ``decode_tokens_per_s``."""

from benchmark.harness import find


def read(run):
    return find("layer_metrics", "decode.step_device_ms").read(run)
