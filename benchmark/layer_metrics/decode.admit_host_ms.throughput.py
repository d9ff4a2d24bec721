"""Median length of one admission on the engine's thread, in a cell whose end-to-end metric is tokens per second:
``decode.admit_host_ms``'s reader (which moves ``itl_p95_ms`` in its own cells), under
the name that moves ``decode_tokens_per_s``."""

from benchmark.harness import find


def read(run):
    return find("layer_metrics", "decode.admit_host_ms").read(run)
