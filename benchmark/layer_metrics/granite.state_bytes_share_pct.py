"""Of the bytes a Granite-4.0-H decode step needs
(``opcount/granite_hybrid_engine.py``), the share that is recurrent state:
every live slot's 36 states and convolution tails read and written, from
the program's row counters over the traced seconds.  The rest is the
weights and the attention layers' K and V rows."""

from benchmark.harness import find


def read(run):
    rows = find("layer_metrics", "granite.step_roofline").rows_a_step(run)
    if rows is None:
        return None
    from benchmark.opcount import granite_hybrid_engine as opcount

    state = opcount.step_state_bytes(run["config"], *rows)["state"]
    return 100.0 * state / opcount.step_bytes(run["config"], *rows)
