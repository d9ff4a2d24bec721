"""Seconds from the start of the process (``window.t0 - setup_s``: the
harness's first line) to the start of the program's ``setup.import`` span:
the interpreter, the harness's own imports, ``import jax`` and the
backend's start (``jax.devices()``), none of it the program's.  From
``setup.unattributed_s``'s one account."""

from benchmark.harness import find


def read(run):
    parts = find("layer_metrics", "setup.unattributed_s").account(run)
    return None if parts is None else parts["before_program_s"]
