"""Read, on the chip and in one process, the numbers a cell's limits are
set from: for each seed one short run of the cell through the harness
(``correct`` as the window decides it) and, with ``--control``, the same
comparison with the reference computed in the nearest lower precision in
the program's place.  Prints one JSON line per seed.

    chiprun -- python benchmark/tools/read_limits.py --workload <cell> \
        --seeds 11,12,13 --seconds 8 --control
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=8.0)
    parser.add_argument("--control", action="store_true")
    opts = parser.parse_args()

    from benchmark import harness
    from benchmark.run import take_chips

    manifest = harness.load_manifest()
    cell, _c, _t = harness.resolve_cell(manifest, opts.workload)
    devices = take_chips(int(cell["chips"]))
    for seed in (int(s) for s in opts.seeds.split(",")):
        t0 = time.monotonic()
        result, compared, control = harness.run_cell(
            manifest, opts.workload, seed, opts.seconds, 0, devices, t0,
            with_control=opts.control)
        print("READ " + json.dumps({
            "seed": seed, "correct": result["correct"],
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "peak": result["device"]["memory_peak_bytes"],
            "compared": {c["name"]: {k: v for k, v in c.items()
                                     if k in ("value", "not_the_best",
                                              "tokens", "requests")}
                         for c in compared},
            "control": [{k: v for k, v in c.items()
                         if not isinstance(v, (list, dict))}
                        for c in control or ()]}),
            flush=True)


if __name__ == "__main__":
    main()
