"""How many times set-up lowers a program for each one it needs: ``lower``
records over ``load`` plus ``compile`` records that ended before the
traffic's ramp (``compile_cache.phases()``).  A ``lower`` record is one
top-level lowering that really ran, a ``load`` or ``compile`` one program
XLA was asked for; 1.0 is the floor.  None where no program was asked
for.  From ``setup.unattributed_s``'s one account."""

from benchmark.harness import find


def read(run):
    parts = find("layer_metrics", "setup.unattributed_s").account(run)
    if parts is None or not parts["programs"]:
        return None
    return parts["lowerings"] / parts["programs"]
