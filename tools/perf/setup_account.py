#!/usr/bin/env python3
"""One run of one benchmark cell, in this process, of the tree in the
CURRENT DIRECTORY, and then where set-up's seconds went, twice:

* **from outside**, with a listener of this tool's own on JAX's three
  duration events and the moment ``mxnet_tpu`` began to import: the same
  reading on any tree, the parent commit too, so two sides of a pair can be
  compared part by part (``before``, ``trace``, ``lower``, ``load``, the
  rest, and ``build``: the first ``prefill`` trace to the last load);
* **the program's own account** where it keeps one (``compile_cache.
  phases()``, ``tracing.setup_spans()``): the six ``setup.*`` metrics' parts
  (``benchmark/layer_metrics/setup.unattributed_s.py``), each set-up span
  with the traces, lowerings and loads inside it, the longest intervals
  nothing names, and what one pass through the listener costs.

Takes ``benchmark/run.py``'s arguments and prints what it prints, the result
line last.  One JSON line of the outside parts is appended to
``$SETUP_ACCOUNT_OUT.jsonl`` and the account written to
``$SETUP_ACCOUNT_OUT.txt`` (default ``chiprun_out/setup_account_<cell>``).
PERF.md section 5's set-up table is made of these.

    cd <tree> && python3 <here>/setup_account.py --workload <cell> --seed <n> --seconds 30 --trace <0|1>
"""

import importlib.util
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.getcwd())

from benchmark import run as bench_run  # noqa: E402 — its clock first

EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
          "/jax/core/compile/backend_compile_duration": "load"}
PARTS = ("before_program_s", "trace_s", "lower_s", "relower_s", "load_s",
         "spans_s", "ramp_s", "unattributed_s", "lowerings", "programs")


def _reader():
    """This tool's copy of the account's arithmetic, whatever the tree."""
    spec = importlib.util.spec_from_file_location(
        "setup_unattributed", os.path.join(
            HERE, "..", "..", "benchmark", "layer_metrics",
            "setup.unattributed_s.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Outside:
    """What can be heard without the program's help."""

    def __init__(self):
        self.heard, self.imported, self.events = [], [], 0

    def listen(self, event, duration, fun_name=None, **_kw):
        self.events += 1
        phase = EVENTS.get(event)
        if phase:
            t1 = time.monotonic()
            self.heard.append((phase, fun_name, t1 - float(duration), t1,
                               threading.get_native_id()))

    def find_spec(self, name, _path=None, _target=None):
        if name == "mxnet_tpu" and not self.imported:
            self.imported.append(time.monotonic())
        return None     # the real finders go on

    def parts(self, run, reader):
        ramp = float(run["traffic"].get("ramp_seconds", 0.0))
        t0 = run["window"]["t0"]
        start, cut = t0 - run["setup_s"], t0 - ramp
        at = self.imported[0]
        parts = reader.parts_of(self.heard, [("setup.import", at, at, 0)],
                                start, cut, ramp)
        parts["rest_s"] = parts.pop("unattributed_s") + parts.pop("spans_s")
        del parts["relower_s"]
        build = [p for p in self.heard if p[2] < cut
                 and p[1] in ("prefill", "step", "jit(prefill)", "jit(step)")]
        parts["build_s"] = max(p[3] for p in build) - min(
            p[2] for p in build) if build else None
        parts["setup_s"] = run["setup_s"]
        parts["events"] = self.events
        return parts


def write_account(run, out, reader):
    from benchmark import trace_reduce
    from mxnet_tpu import compile_cache, tracing

    parts = reader.account(dict(run, trace=run["trace"] or {}))
    if parts is None:
        out.write("the program keeps no account of its own\n")
        return
    t0 = run["window"]["t0"]
    start, cut = t0 - run["setup_s"], t0 - parts["ramp_s"]
    heard = [p for p in compile_cache.phases() if p[2] < cut]
    spans = sorted((r for r in tracing.setup_spans()
                    if r["t0_ns"] * 1e-9 < cut), key=lambda r: r["t0_ns"])
    out.write("%s: setup_s %.3f\n" % (run["cell"]["name"], run["setup_s"]))
    for key in PARTS:
        out.write("  %-18s %10.3f\n" % (key, parts[key]))
    out.write("records kept: %d, %d dropped (%d before the ramp)\n"
              % (len(compile_cache.phases()),
                 compile_cache.stats().get("phases_dropped", 0), len(heard)))
    out.write("\nset-up spans (start after the process's, seconds, then "
              "the trace | lower | load | compile inside, same thread):\n")
    for r in spans:
        a, b = r["t0_ns"] * 1e-9, r["t1_ns"] * 1e-9
        inside = [reader._seconds(reader._clip(
            [(c, d) for p, _fn, c, d, tid in heard
             if p == phase and tid == r["tid"]], a, b))
            for phase in ("trace", "lower", "load", "compile")]
        out.write("  %8.3f %8.3f  %-26s %s  %s\n" % (
            a - start, b - a, r["name"],
            " | ".join("%.3f" % s for s in inside),
            " ".join("%s=%s" % kv for kv in sorted(r["attrs"].items()))))
    named = trace_reduce.union(
        [(start, min(r["t0_ns"] * 1e-9 for r in spans
                     if r["name"] == "setup.import"))]
        + [(r["t0_ns"] * 1e-9, min(r["t1_ns"] * 1e-9, cut)) for r in spans]
        + [(p[2], min(p[3], cut)) for p in heard])
    gaps = [(b, c) for (_a, b), (c, _d) in zip(named, named[1:] + [
        [cut, cut]]) if c > b]
    out.write("\nthe longest intervals nothing names (start, seconds, the "
              "record that ends before and the one that starts after):\n")
    ends = sorted([(p[3], "%s %s" % p[:2]) for p in heard]
                  + [(r["t1_ns"] * 1e-9, r["name"]) for r in spans])
    begins = sorted([(p[2], "%s %s" % p[:2]) for p in heard]
                    + [(r["t0_ns"] * 1e-9, r["name"]) for r in spans])
    for b, c in sorted(gaps, key=lambda g: g[0] - g[1])[:8]:
        before = [n for t, n in ends if t <= b + 1e-6][-1:] or ["start"]
        after = [n for t, n in begins if t >= c - 1e-6][:1] or ["the ramp"]
        out.write("  %8.3f %8.3f  after %s, before %s\n"
                  % (b - start, c - b, before[0], after[0]))
    out.write("\nthe longest traces, lowerings and loads before the ramp:\n")
    for p in sorted(heard, key=lambda p: p[2] - p[3])[:16]:
        out.write("  %8.3f %8.3f  %-7s %s\n"
                  % (p[2] - start, p[3] - p[2], p[0], p[1]))
    n = 2000    # fewer than phases() has room for
    t = time.perf_counter()
    for _ in range(n):
        compile_cache._on_duration(compile_cache._EVENT_TRACE, 1e-6,
                                   fun_name="x")
    each = (time.perf_counter() - t) / n
    out.write("\none pass through compile_cache._on_duration: %.2f us\n"
              % (each * 1e6))


def main():
    import jax.monitoring

    from benchmark import harness

    outside, kept = Outside(), {}
    jax.monitoring.register_event_duration_secs_listener(outside.listen)
    sys.meta_path.insert(0, outside)
    find = harness.find

    def keeping(kind, name):
        module = find(kind, name)
        for entry in ("read", "compute"):
            if kind in ("layer_metrics", "end_to_end") \
                    and hasattr(module, entry):
                real = getattr(module, entry)
                setattr(module, entry, lambda run, real=real: (
                    kept.setdefault("run", run), real(run))[1])
        return module

    harness.find = keeping
    try:
        bench_run.main()
    finally:
        if "run" in kept:
            run, reader = kept["run"], _reader()
            base = os.environ.get("SETUP_ACCOUNT_OUT") or os.path.join(
                "chiprun_out", "setup_account_%s" % run["cell"]["name"])
            os.makedirs(os.path.dirname(base) or ".", exist_ok=True)
            parts = outside.parts(run, reader)
            parts.update(tree=os.path.basename(os.getcwd()),
                         note=os.environ.get("SETUP_ACCOUNT_NOTE"),
                         cell=run["cell"]["name"],
                         traced=run["trace"] is not None)
            with open(base + ".jsonl", "a") as out:
                out.write(json.dumps(parts) + "\n")
            with open(base + ".txt", "w") as out:
                out.write("heard from outside: %s\n\n" % json.dumps(parts))
                write_account(run, out, reader)


if __name__ == "__main__":
    main()
