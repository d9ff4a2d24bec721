"""One traced run of a cell on the chip, in process, with the device's
operation groups printed down to the fiftieth and not the tenth: where a
step's and a prefill's time goes, for ``PERF.md`` section 5.

    chiprun -- python benchmark/tools/read_breakdown.py --workload <cell> --seed 7
"""

import argparse
import functools
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
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=30.0)
    opts = parser.parse_args()

    from benchmark import harness, trace_reduce
    from benchmark.run import take_chips

    trace_reduce.reduce = functools.partial(trace_reduce.reduce, top=50)
    manifest = harness.load_manifest()
    cell, _c, _t = harness.resolve_cell(manifest, opts.workload)
    devices = take_chips(int(cell["chips"]))
    result, _compared, _control = harness.run_cell(
        manifest, opts.workload, opts.seed, opts.seconds, 1, devices,
        time.monotonic())
    busy = result["device"]["busy_s"]
    for group, seconds in result["breakdown"]["device_ops"]:
        print("OP %6.2f%% %9.6f s  %s" % (100.0 * seconds / busy, seconds,
                                           group), flush=True)
    result["breakdown"]["device_ops"] = result["breakdown"]["device_ops"][:10]
    print("RESULT " + json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
