"""What the host adds to a decode step, in a cell whose end-to-end metric is tokens per second:
``decode.host_ms_per_step``'s reader (which moves ``itl_p95_ms`` in its own cells), under
the name that moves ``decode_tokens_per_s``."""

from benchmark.harness import find


def read(run):
    return find("layer_metrics", "decode.host_ms_per_step").read(run)
