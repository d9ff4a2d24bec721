#!/usr/bin/env python3
"""One traced run of ``gpt2-large.saturate``, in this process, of the tree
in the CURRENT DIRECTORY, with the program's telemetry on: how many of a
slot's rows the decode step's attention read, twice.

* **by the engine** (``DecodeEngine.model_counters()``, the gauge
  ``serving.attn.rows_read_share`` from the host's mirror of the lengths and
  ``transformer_lm.ladder``), over the window up to the traced seconds and
  over the traced seconds alone;
* **by the device trace** of those seconds: the scores of a rung of ``R``
  rows are the group ``multiply_reduce_fusion f32[12,20,R]``, so its seconds
  over ``R`` count the steps that took the rung, and the rows read follow.

``ops.kernel_path{op="attend_slots"}`` says which path the step traced (on a
start that loads the step from the executable store, the count it stored).
Takes ``benchmark/run.py``'s arguments but ``--trace`` and prints what it
prints, the result line last; a tree whose model offers no
``attended_rows`` prints no gauge.

    cd <tree> && python3 <here>/attended_rows.py --seed <n> --seconds 30
"""

import json
import os
import re
import sys

sys.path.insert(0, os.getcwd())

from benchmark import run as bench_run  # noqa: E402 — its clock first

CELL = "gpt2-large.saturate"


def main():
    import argparse

    from benchmark import harness
    from mxnet_tpu import telemetry

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    opts = parser.parse_args()
    telemetry.enable()
    gauges, traced = [], []
    find, reduce = harness.find, harness.Tracer.reduce

    def spying(kind, name):
        module = find(kind, name)
        if kind == "families":
            counters = module.System.counters

            def spy(self):
                # the tracer reads the counters as the trace comes on and
                # as it goes off, the harness once more after the window
                gauges.append(self.engine.model_counters().get(
                    "gauges", {}).get("serving.attn.rows_read_share"))
                return counters(self)

            module.System.counters = spy
        return module

    def kept(self):
        out = reduce(self)
        traced.append(out)
        return out

    harness.find, harness.Tracer.reduce = spying, kept
    manifest = harness.load_manifest()
    cell, config, _traffic = harness.resolve_cell(manifest, CELL)
    result, _compared, _control = harness.run_cell(
        manifest, CELL, opts.seed, opts.seconds, 1,
        bench_run.take_chips(int(cell["chips"])), bench_run.T_PROCESS)
    paths = telemetry.snapshot()["counters"].get("ops.kernel_path", {})
    print("KERNEL_PATH %s" % json.dumps(
        {k: v for k, v in paths.items() if "attend_slots" in k}))
    print("GAUGE serving.attn.rows_read_share: %s up to the traced seconds, "
          "%s over them" % tuple(gauges[:2]))
    rungs = {}
    for group, seconds in traced[0]["devices"][0]["op_seconds"].items():
        m = re.fullmatch(r"multiply_reduce_fusion f32\[%d,%d,(\d+)\]" % (
            int(config["engine"]["slots"]), int(config["n_head"])), group)
        if m:
            rungs[int(m.group(1))] = seconds
    if rungs:
        steps = {r: s / r for r, s in rungs.items()}
        held = int(config["n_positions"])
        print("TRACE seconds of the scores by rung: %s; rows read over %d: "
              "%.4f" % (json.dumps(dict(sorted(rungs.items()))), held,
                        sum(rungs.values()) / (held * sum(steps.values()))))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
