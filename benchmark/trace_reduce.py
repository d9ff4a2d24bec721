"""From the profiler's ``.xplane.pb`` to what the per-layer metrics read:
each device's busy time (the union of the intervals in which an operation
ran), the time of every operation group (self time: a ``while`` does not
count its body twice), every program launch, and the idle gaps named by
what the host was doing in them.  Read with ``jax.profiler.ProfileData``
alone; checked on the recorded trace in ``trace_sample/``
(``tools/record_sample_trace.py`` made it on a v5e chip).

What the v5e trace holds: a plane ``/device:TPU:<n>`` per chip with the
lines ``XLA Modules`` (one event per program launch, named
``jit_<fn>(<fingerprint>)``), ``XLA Ops`` (one event per HLO operation,
named by its HLO text) and ``Async XLA Ops`` (copies that overlap other
operations: not counted as busy on their own); a plane ``/host:CPU`` with a
line per host thread, where ``jax.profiler.TraceAnnotation`` spans of the
harness appear under their own names (``bench.*``).  Times are nanoseconds
on one clock for every plane."""

import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HARNESS_SPAN = "bench."
NS = 1e-9
#: idle gaps named per device: the longest ones.  Naming looks through the
#: host's events for each gap, and a training step on four chips leaves
#: tens of thousands of gaps a few hundred nanoseconds long
NAMED_GAPS = 200
UNNAMED = "(shorter gaps, not named)"


def op_kind(event_name):
    """``%fusion.16 = (u32[1]...) fusion(...)`` -> ``fusion``: the HLO
    instruction's name without ``%`` and without its numeric suffix."""
    name = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", name)


def op_group(event_name):
    """The group an operation's time is summed under: its kind and the
    type of its (first) result, ``fusion f32[256,64,56,56]``, so that the
    launches of one kind of operation on one shape fall together and an
    unnamed ``fusion`` of one stage is told from another's."""
    kind = op_kind(event_name)
    m = re.search(r" = \(?([a-z0-9]+\[[0-9,]*\])", event_name)
    return "%s %s" % (kind, m.group(1)) if m else kind


def module_name(event_name):
    """``jit_step(11937236725742203718)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def union(intervals):
    """Sorted, merged (start, end) pairs."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def self_times(events):
    """Seconds of each group with nested events counted once: an event
    that starts inside another is its child, and the parent keeps only what
    its children leave.  ``events``: (name, start, duration)."""
    out = defaultdict(float)
    stack = []              # (end, group, self_ns)
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            _end, group, own = stack.pop()
            out[group] += own * NS
        if stack:
            stack[-1][2] -= dur
        stack.append([start + dur, op_group(name), dur])
    for _end, group, own in stack:
        out[group] += own * NS
    return dict(out)


def _innermost(spans, at):
    best = None
    for name, start, dur in spans:
        if start <= at <= start + dur and (best is None or dur < best[1]):
            best = (name, dur)
    return best[0] if best else None


def reduce(path, top=10):
    """Reduce one trace file.  Returns ``busy_s`` (mean over the device
    planes), ``devices`` (per plane: ``busy_s``, ``span_s`` from its first
    to its last operation, ``op_seconds``, ``modules`` as (name, start_s,
    seconds)), ``harness_spans`` and the
    ``breakdown`` the result line carries."""
    from jax.profiler import ProfileData

    devices, harness, runtime = [], [], []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            ops = [(e.name, e.start_ns, e.duration_ns)
                   for e in lines[OPS_LINE].events]
            modules = [(module_name(e.name), e.start_ns, e.duration_ns)
                       for e in lines[MODULES_LINE].events] \
                if MODULES_LINE in lines else []
            devices.append((plane.name, ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HARNESS_SPAN):
                        harness.append((e.name, e.start_ns, e.duration_ns))
                    elif not e.name.startswith("$") and e.duration_ns > 0:
                        runtime.append((e.name, e.start_ns, e.duration_ns))
    out_devices, gap_seconds, op_totals = [], defaultdict(float), \
        defaultdict(float)
    for name, ops, modules in sorted(devices):
        busy = union((s, s + d) for _n, s, d in ops if d > 0)
        busy_ns = sum(e - s for s, e in busy)
        groups = self_times(ops)
        for g, sec in groups.items():
            op_totals[g] += sec
        gaps = sorted(((start - end, (end + start) / 2.0)
                       for (_s0, end), (start, _e1) in zip(busy, busy[1:])),
                      reverse=True)
        for length, at in gaps[:NAMED_GAPS]:
            what = _innermost(harness, at) or "-"
            doing = _innermost(runtime, at) or "no runtime call"
            gap_seconds["%s / %s" % (what, doing)] += length * NS
        if gaps[NAMED_GAPS:]:
            gap_seconds[UNNAMED] += sum(
                g for g, _at in gaps[NAMED_GAPS:]) * NS
        out_devices.append({
            "plane": name, "busy_s": busy_ns * NS,
            "span_s": (busy[-1][1] - busy[0][0]) * NS if busy else 0.0,
            "op_seconds": groups,
            "modules": [(n, s * NS, d * NS) for n, s, d in modules]})
    if not out_devices:
        raise ValueError("no device plane with an %r line in %s"
                         % (OPS_LINE, path))
    n = len(out_devices)

    def ranked(table, scale=1.0):
        return [[k, v * scale] for k, v in sorted(
            table.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": sum(d["busy_s"] for d in out_devices) / n,
            "devices": out_devices,
            "harness_spans": [(nm, s * NS, d * NS) for nm, s, d in harness],
            "breakdown": {"device_ops": ranked(op_totals, 1.0 / n),
                          "idle_gaps": ranked(gap_seconds, 1.0 / n)}}


def idle_pct(trace):
    """Share of the traced window in which no operation ran on the device,
    the least busy device where there are several."""
    least_busy = min(d["busy_s"] for d in trace["devices"])
    return 100.0 * (1.0 - least_busy / trace["window_s"])
