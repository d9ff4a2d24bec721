#!/usr/bin/env python
"""Per-HLO device-time profile of the benchmarked training step.

Captures a ``jax.profiler`` trace around ``Module.run_bulk`` — the SAME
compiled fwd+bwd+update step ``bench.py`` times (imports ``bench.setup``)
— then parses the device-side xplane events out of the emitted
``*.trace.json.gz`` and aggregates them into:

  * a per-HLO table: device time/step, % of step, achieved TFLOP/s and
    HBM GB/s for that op (from the profiler's ``model_flops`` /
    ``bytes_accessed``), and the op's output shape+layout;
  * a category rollup (convolution fusion / loop fusion / copy / ...).

This is the ground-truth answer to "where do the milliseconds go" that
wall-clock ablations can only approximate: every row is the TPU's own
picosecond timestamp for one HLO, so dispatch latency cannot contaminate
the attribution (a slow host stretches the *gaps*, not the op durations).

Usage:
    python tools/perf/step_profile.py                # print tables
    python tools/perf/step_profile.py --json out.json
    BENCH_BULK=10 BENCH_DTYPE=bfloat16 ... all bench env vars apply

The reference's analog is nvprof over its executor (its perf guide
``docs/how_to/perf.md`` drives everything from throughput numbers; the
per-kernel view there is cuDNN's job).  On TPU the XLA profiler is the
only window into the fused schedule, so it is a first-class tool here.
"""

import argparse
import collections
import glob
import gzip
import json
import os
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, _REPO)


def capture(steps, tracedir, model="resnet"):
    import bench

    if model == "resnet":
        mod, run, sync = bench.setup()
        warm = 2 * bench.BULK
    elif model == "ssd":
        import bench_extra

        mod, run, sync = bench_extra.ssd_setup()
        warm = steps
    else:
        raise SystemExit("unknown --model %r" % model)
    # compile + warm every jit path before the trace window opens
    run(warm)
    sync()

    import jax.profiler

    jax.profiler.start_trace(tracedir)
    run(steps)
    sync()
    jax.profiler.stop_trace()
    return mod


def load_device_events(tracedir):
    """All device-side per-HLO events (those carrying hlo_category)."""
    paths = glob.glob(os.path.join(
        tracedir, "plugins", "profile", "*", "*.trace.json.gz"))
    if not paths:
        raise RuntimeError("no trace.json.gz under %s" % tracedir)
    data = json.load(gzip.open(max(paths), "rt"))
    evs = data.get("traceEvents", [])
    pids = {e["pid"]: e["args"]["name"] for e in evs
            if e.get("ph") == "M" and e.get("name") == "process_name"
            and "args" in e}
    dev_pids = {p for p, n in pids.items() if "TPU" in n or "device" in n}
    out = []
    for e in evs:
        if e.get("ph") != "X" or e.get("pid") not in dev_pids:
            continue
        args = e.get("args") or {}
        if "hlo_category" not in args:
            continue  # container events (whole-executable spans)
        out.append(e)
    return out, data


def aggregate(events, steps):
    """Aggregate per-HLO events into per-step rows keyed by op name."""
    rows = {}
    for e in events:
        a = e["args"]
        name = e["name"]
        r = rows.setdefault(name, {
            "name": name, "category": a.get("hlo_category", "?"),
            "dur_ps": 0, "count": 0, "flops": 0, "bytes": 0,
            "long_name": a.get("long_name", "")})
        dur = int(a.get("device_duration_ps", 0)) or int(
            e.get("dur", 0) * 1e6)
        r["dur_ps"] += dur
        r["count"] += 1
        r["flops"] += int(a.get("model_flops", 0) or 0)
        r["bytes"] += int(a.get("raw_bytes_accessed",
                                a.get("bytes_accessed", 0)) or 0)
    for r in rows.values():
        r["us_per_step"] = r["dur_ps"] / 1e6 / steps
        r["tflops"] = (r["flops"] / (r["dur_ps"] / 1e12) / 1e12
                       if r["dur_ps"] and r["flops"] else 0.0)
        r["gbps"] = (r["bytes"] / (r["dur_ps"] / 1e12) / 1e9
                     if r["dur_ps"] else 0.0)
    return sorted(rows.values(), key=lambda r: -r["dur_ps"])


def shape_of(long_name):
    """Output shape+layout chunk of an HLO long_name ('%x = HERE op(...)')."""
    if "=" not in long_name:
        return ""
    rhs = long_name.split("=", 1)[1].strip()
    depth = 0
    for i, c in enumerate(rhs):
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == " " and depth == 0:
            return rhs[:i]
    return rhs[:60]


def render(rows, steps, top):
    total_us = sum(r["dur_ps"] for r in rows) / 1e6 / steps
    lines = []
    lines.append("device HLO time: %.1f us/step over %d steps"
                 % (total_us, steps))
    lines.append("")
    lines.append("| HLO | category | us/step | % | runs/step | TFLOP/s |"
                 " GB/s | output |")
    lines.append("|---|---|---|---|---|---|---|---|")
    for r in rows[:top]:
        lines.append(
            "| %s | %s | %.1f | %.1f%% | %.0f | %s | %.0f | `%s` |" % (
                r["name"][:46], r["category"], r["us_per_step"],
                100.0 * r["us_per_step"] / total_us,
                r["count"] / steps,
                ("%.1f" % r["tflops"]) if r["tflops"] else "-",
                r["gbps"], shape_of(r["long_name"])[:48]))
    rest = rows[top:]
    if rest:
        rest_us = sum(r["dur_ps"] for r in rest) / 1e6 / steps
        lines.append("| (%d more) |  | %.1f | %.1f%% |  |  |  |  |"
                     % (len(rest), rest_us, 100.0 * rest_us / total_us))
    lines.append("")
    cats = collections.defaultdict(lambda: [0, 0, 0])
    for r in rows:
        c = cats[r["category"]]
        c[0] += r["dur_ps"]
        c[1] += r["flops"]
        c[2] += r["bytes"]
    lines.append("| category | us/step | % | TFLOP/s | GB/s |")
    lines.append("|---|---|---|---|---|")
    for cat, (ps, fl, by) in sorted(cats.items(), key=lambda kv: -kv[1][0]):
        us = ps / 1e6 / steps
        lines.append("| %s | %.1f | %.1f%% | %s | %.0f |" % (
            cat, us, 100.0 * us / total_us,
            ("%.1f" % (fl / (ps / 1e12) / 1e12)) if fl else "-",
            by / (ps / 1e12) / 1e9 if ps else 0))
    return "\n".join(lines), total_us


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int,
                    default=int(os.environ.get("BENCH_BULK", "10")))
    ap.add_argument("--top", type=int, default=40)
    ap.add_argument("--model", default="resnet",
                    choices=("resnet", "ssd"),
                    help="which benched step to profile")
    ap.add_argument("--json", help="also dump aggregated rows as JSON")
    ap.add_argument("--keep-trace", action="store_true")
    args = ap.parse_args()

    tracedir = tempfile.mkdtemp(prefix="step_profile_")
    mod = capture(args.steps, tracedir, args.model)
    events, _ = load_device_events(tracedir)
    rows = aggregate(events, args.steps)
    table, total_us = render(rows, args.steps, args.top)
    print(table)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"steps": args.steps, "total_us_per_step": total_us,
                       "rows": rows}, f, indent=1)
        # the SAME executable's HLO (jit-cache hit on the recorded bulk
        # signature) so tools/perf/hlo_bytes.py matches fusion names
        # exactly — a fresh-process recompile renumbers fusions
        try:
            fn, avals = mod._last_bulk_sig
            with open(args.json + ".hlo.txt", "w") as f:
                f.write(fn.lower(*avals).compile().as_text())
            print("hlo text:", args.json + ".hlo.txt", file=sys.stderr)
        except Exception as e:  # profiling still useful without it
            print("hlo dump failed: %s" % e, file=sys.stderr)
    if not args.keep_trace:
        import shutil

        shutil.rmtree(tracedir, ignore_errors=True)
    else:
        print("\ntrace kept at", tracedir, file=sys.stderr)


if __name__ == "__main__":
    main()
