"""Seconds of set-up, before the traffic's ramp, that the host spent
lowering jaxprs to MLIR modules: the union of the ``lower`` intervals the
program heard from JAX (``compile_cache.phases()``: one a top-level
lowering that really ran; a kernel's lowering traces inside it and those
traces are its own), less what lies inside a load.  From
``setup.unattributed_s``'s one account."""

from benchmark.harness import find


def read(run):
    parts = find("layer_metrics", "setup.unattributed_s").account(run)
    return None if parts is None else parts["lower_s"]
