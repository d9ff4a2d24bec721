"""The part of ``setup.trace_s`` and ``setup.lower_s`` that lies inside the
program's ``compile_cache.note_build`` spans, on the thread that ran them:
what recording a build into the warm-up manifest traces and lowers again
for the fingerprint alone.  Near zero where that second lowering hits
JAX's own cache.  From ``setup.unattributed_s``'s one account."""

from benchmark.harness import find


def read(run):
    parts = find("layer_metrics", "setup.unattributed_s").account(run)
    return None if parts is None else parts["relower_s"]
