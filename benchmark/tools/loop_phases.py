#!/usr/bin/env python3
"""One traced run of a cell, and then the program's own spans of the
traced seconds by name: how many, the median, the 95th percentile and the
sum of their lengths, per thread (``PERF.md`` section 5 is written from
this).  The result line comes first, as ``run.py`` prints it; the records
are kept as ``chiprun_out/spans_<cell>_<seed>.jsonl``.

    chiprun -- python3 benchmark/tools/loop_phases.py --workload <cell> --seed <n>
"""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402 — the clock starts before everything else
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=30.0)
    opts = parser.parse_args()

    from benchmark import harness, stats
    from benchmark.run import take_chips
    from mxnet_tpu import tracing

    manifest = harness.load_manifest()
    cell, _config, _traffic = harness.resolve_cell(manifest, opts.workload)
    result, _compared, _control = harness.run_cell(
        manifest, opts.workload, opts.seed, opts.seconds, 1,
        take_chips(int(cell["chips"])), T_PROCESS)
    print(json.dumps(result), flush=True)
    # recording was on while the device was profiled, and only then
    spans = tracing.spans_recent(1 << 20)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "spans_%s_%d.jsonl"
                           % (opts.workload, opts.seed)), "w") as f:
        for r in spans:
            f.write(json.dumps(r) + "\n")
    by_id = {r["span_id"]: r for r in spans}
    groups = {}
    for r in spans:
        parent = by_id.get(r["parent_id"])
        key = (r["tid"], r["name"], r["attrs"].get("site", ""),
               parent["name"] if parent else "-")
        groups.setdefault(key, []).append(1e3 * r["dur_s"])
    print("%-8s %-28s %-20s %-22s %6s %10s %10s %10s"
          % ("thread", "span", "site", "under", "n", "median_ms",
             "p95_ms", "sum_ms"))
    for (tid, name, site, under), ms in sorted(groups.items()):
        print("%-8d %-28s %-20s %-22s %6d %10.3f %10.3f %10.1f"
              % (tid, name, site, under, len(ms), statistics.median(ms),
                 stats.percentile(ms, 95.0), sum(ms)))


if __name__ == "__main__":
    main()
