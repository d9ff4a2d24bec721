#!/usr/bin/env python3
"""One run of one benchmark cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s>
                             --trace <0|1>

A new process that takes the cell's chips (no chip is an error, never a CPU
fallback), builds weights and traffic from ``--seed``, warms the cell's own
programs, measures for ``--seconds``, checks what the window produced
against the plain reference, prints what it compared and, LAST, the one
JSON line of the contract.  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` is a run of its own that profiles the last seconds
of the window and reports its per-layer metrics.  ``BENCH_RUN`` in the
environment is ignored.  See ``benchmark/README.md``."""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402 — the clock starts before everything else
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def take_chips(chips):
    """The first ``chips`` TPU devices, or an error."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit(
            "this cell needs %d TPU chip(s); JAX reports %d %s device(s)"
            % (chips, len(devices), devices[0].platform))
    return devices[:chips]


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    opts = parser.parse_args()

    from benchmark import harness

    manifest = harness.load_manifest()
    cell, _config, _traffic = harness.resolve_cell(manifest, opts.workload)
    devices = take_chips(int(cell["chips"]))
    harness.peaks_of(devices[0].device_kind)    # an unknown kind raises
    result, _compared, _control = harness.run_cell(
        manifest, opts.workload, opts.seed, opts.seconds, opts.trace,
        devices, T_PROCESS)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
