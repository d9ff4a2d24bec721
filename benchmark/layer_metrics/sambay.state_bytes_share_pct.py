"""Of the bytes a decode step needs (``opcount/sambay_engine.py``), the
share that is slot state and not weights: the recurrent states and tails,
the rings' rows and the shared full layer's rows times its eight readers,
from the program's row counters over the traced seconds."""

from benchmark.harness import find
from benchmark.opcount import sambay_engine as opcount


def read(run):
    rows = find("layer_metrics", "sambay.step_roofline").rows_a_step(run)
    if rows is None:
        return None
    state = sum(opcount.step_state_bytes(run["config"], *rows).values())
    return 100.0 * state / opcount.step_bytes(run["config"], *rows)
