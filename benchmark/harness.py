"""The harness: everything between ``run.py``'s look for a chip and its one
result line.  It holds no cell's name and no configuration's name: a cell
is names in ``BENCHMARK.json``, and each name is resolved to a file here:

* ``configs/<config>.json``      sizes, precision, limits; ``family`` names
* ``families/<family>.py``       the adapter of one program path
* ``traffic/<traffic>.json``     parameters; ``kind`` names
* ``generators/<kind>.py``       the general generator that reads them
* ``end_to_end/<metric>.py``     ``compute(run)``
* ``layer_metrics/<metric>.py``  ``read(run)`` — None when nothing to read
"""

import gc
import importlib.util
import json
import os
import shutil
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: where a traced run keeps its profile: inside the checkout, git-ignored
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
#: a traced run profiles the last seconds of the window
TRACE_SECONDS = 3.0


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_manifest():
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def find(kind, name):
    """The module ``<kind>/<name>.py`` beside this file.  Names carry dots
    and dashes, so they are loaded by path, not imported by name."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError("no %s named %r (%s)" % (kind, name, path))
    spec = importlib.util.spec_from_file_location(
        "benchmark_%s_%s" % (kind, "".join(c if c.isalnum() else "_"
                                           for c in name)), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def resolve_cell(manifest, workload):
    """(cell, config, traffic) of a workload name: the two files read."""
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise KeyError("no workload %r in BENCHMARK.json (has: %s)"
                       % (workload, ", ".join(sorted(cells))))
    cell = cells[workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    return cell, config, traffic


def metrics_of(manifest, group, workload):
    """Names of the ``group`` metrics this workload may report: those that
    list it, and those that list no workloads at all."""
    return [m["name"] for m in manifest[group]
            if workload in m.get("workloads", [workload])]


def peaks_of(device_kind):
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            "device kind %r is not in benchmark/peaks.json: add its "
            "published peaks with their source, never a default"
            % device_kind)
    return table[device_kind]


class Tracer:
    """Profiles the last ``TRACE_SECONDS`` of the window in a traced run;
    does nothing otherwise.  Generators call :meth:`poll` with the seconds
    since the window opened and :meth:`close` when it closes."""

    def __init__(self, on, seconds, name, counters):
        self.on = on
        self.start_at = max(0.0, seconds - TRACE_SECONDS)
        self.dir = os.path.join(TRACE_DIR, name)
        self.counters = counters    # the system's, read at both ends
        self.t_start = self.t_stop = None

    def poll(self, elapsed):
        if self.on and self.t_start is None and elapsed >= self.start_at:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            # the Python tracer would record every call of the host's
            # threads and slow the very loop that is measured
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.t_start = time.monotonic()
            self.counters_start = self.counters()

    def close(self):
        if self.t_start is not None and self.t_stop is None:
            import jax

            self.counters_stop = self.counters()
            self.t_stop = time.monotonic()
            jax.profiler.stop_trace()

    def reduce(self):
        """The trace reduced (``trace_reduce.reduce``), or None."""
        if self.t_stop is None:
            return None
        import glob

        from benchmark import trace_reduce

        (path,) = glob.glob(os.path.join(self.dir, "plugins", "profile",
                                         "*", "*.xplane.pb"))
        out = trace_reduce.reduce(path)
        out["window_s"] = self.t_stop - self.t_start
        out["counted"] = {k: v - self.counters_start[k]
                          for k, v in self.counters_stop.items()}
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


def annotate(name):
    """A span of the harness's own on the profiler's clock."""
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)


def _xla_compiles():
    """How many programs XLA has compiled or loaded in this process: every
    compile consults the persistent cache, so hits plus misses count them."""
    from mxnet_tpu import compile_cache

    s = compile_cache.stats()
    return s["hits"] + s["misses"], s


def run_cell(manifest, workload, seed, seconds, trace, devices, t_process,
             with_control=False, cell_files=None):
    """Drive one run of one cell on ``devices`` and return the result line
    as a dict (plus ``compared``/``control`` for the tools and tests).
    ``cell_files`` (cell, config, traffic) stands in for the manifest's
    files in the CPU-sized tests."""
    import jax

    cell, config, traffic = cell_files or resolve_cell(manifest, workload)
    family = find("families", config["family"])
    generator = find("generators", traffic["kind"])
    plan = generator.plan(traffic, seed, seconds, config)
    system = family.System(config, traffic, seed, devices)
    tracer = Tracer(bool(trace), seconds, cell["name"], system.counters)
    compiles0, cache0 = _xla_compiles()
    collections0 = [g["collections"] for g in gc.get_stats()]
    window = generator.drive(system, plan, traffic, seconds, tracer)
    compiles1, cache1 = _xla_compiles()
    # a full collection stops every Python thread, the server's loop too
    print("garbage collections while the traffic ran, by generation: %s"
          % [g["collections"] - c0
             for g, c0 in zip(gc.get_stats(), collections0)], flush=True)
    held = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    # the allocator's peak counts the arrays held, not the scratch a
    # running program takes beside them: the adapter reads that from the
    # compiled program
    scratch = system.scratch_bytes() if devices[0].platform != "cpu" else 0
    peak = held + scratch
    print("device memory: %d bytes held at the peak + %d bytes of program "
          "scratch = %d" % (held, scratch, peak), flush=True)
    counters = system.counters()
    system.close()
    run = {"cell": cell, "config": config, "traffic": traffic,
           "window": window, "counters": counters, "slots": getattr(
               system, "slots", None),
           "setup_s": window["t0"] - t_process,
           "window_compiles": compiles1 - compiles0,
           "chips": len(devices), "peaks": peaks_of(devices[0].device_kind)
           if devices[0].platform != "cpu" else None,
           "trace": tracer.reduce()}
    print("set-up %.3f s; persistent cache before the window: %d hits, %d "
          "misses; compiles inside the window: %d"
          % (run["setup_s"], cache0["hits"], cache0["misses"],
             run["window_compiles"]), flush=True)
    t_check = time.monotonic()
    compared, control = system.check(window, with_control=with_control)
    compared.append({"name": "window_compiles",
                     "value": run["window_compiles"], "limit": 0,
                     "ok": run["window_compiles"] == 0})
    for c in compared:
        print("compared %s" % json.dumps(c), flush=True)
    for c in control or ():
        print("control %s" % json.dumps(c), flush=True)
    print("reference check took %.3f s" % (time.monotonic() - t_check),
          flush=True)
    group, kind = ("per_layer", "layer_metrics") if trace \
        else ("end_to_end", "end_to_end")
    units = {m["name"]: m["unit"] for m in manifest[group]}
    metrics = {}
    for name in metrics_of(manifest, group, cell["name"]):
        module = find(kind, name)
        value = module.read(run) if trace else module.compute(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": all(c["ok"] for c in compared),
              "attempted": window["attempted"], "failed": window["failed"],
              "metrics": metrics, "device": device}
    if run["trace"] is not None:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        result["breakdown"] = run["trace"]["breakdown"]
    return result, compared, control
