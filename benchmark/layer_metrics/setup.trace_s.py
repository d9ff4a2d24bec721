"""Seconds of set-up, before the traffic's ramp, that the host spent
tracing jitted functions to jaxprs: the union of the ``trace`` intervals
the program heard from JAX (``compile_cache.phases()``: the outermost
trace of nested ones), less what of them lies inside a lowering or a load,
so that the parts of ``setup.unattributed_s``'s account add up.  A warm
start still pays all of it: the persistent cache is keyed by the lowered
text."""

from benchmark.harness import find


def read(run):
    parts = find("layer_metrics", "setup.unattributed_s").account(run)
    return None if parts is None else parts["trace_s"]
