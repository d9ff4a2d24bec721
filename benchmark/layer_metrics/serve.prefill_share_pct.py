"""Share of the device's busy time, in the traced seconds, spent in prefill
programs (``jit_prefill`` launches on the trace's ``XLA Modules`` line):
what admissions take from decoding when tokens per second are the bill."""


def read(run):
    trace = run["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    prefill = sum(d for name, _s, d in trace["devices"][0]["modules"]
                  if name == "jit_prefill")
    return 100.0 * prefill / trace["devices"][0]["busy_s"]
