#!/usr/bin/env python
"""Benchmark: ResNet-50 training throughput on one TPU chip.

Baseline (BASELINE.md): reference MXNet trains ResNet-50/ImageNet at 45.52
images/sec on one K80 (``docs/how_to/perf.md:108-117``).  This harness is the
analog of ``example/image-classification/common/fit.py --benchmark 1``:
synthetic data, full fwd+bwd+SGD-momentum update through ``Module``.

Steps are dispatched in bulks of BENCH_BULK (``Module.run_bulk`` — K real
training steps scanned inside one XLA computation, the TPU analog of the
reference's MXNET_EXEC_BULK_EXEC_TRAIN op bulking) so per-step dispatch
does not pollute the compute measurement.

It runs on the chip and fails without one; ``JAX_PLATFORMS=cpu`` asks for
the CPU by name (``mx.context.measurement_context``), and the row then
says so in its ``device`` field and carries no MFU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "tflops",
"flops_per_img", "value_best", "repeats", "phase_breakdown", "device",
"mfu_pct", "peak_tflops", "hlo_fingerprint", "cost_gflops",
"hbm_peak_bytes"}.  "value" is the median of BENCH_REPEATS timed windows,
"value_best" the fastest one.  The peak comes from
``perfdebug.PEAK_TFLOPS_BY_KIND`` keyed by the device's ``device_kind``;
a TPU kind missing from that table fails the run.

FLOPs are measured from XLA cost analysis of the COMPILED bulk step (the
scan body counts once = one training step; 2 flops per MAC — the same
convention as the chip's peak rating).  Compiling the AOT-lowered step a
second time keeps the count post-optimization (pre-DCE counts would
include dead primal convs from the conv custom_vjp).  A program that
yields no cost analysis fails the run: there is no estimate to fall back
on.

"phase_breakdown" attributes the measured step time to phases via the
telemetry registry (docs/observability.md): input stacking vs XLA
dispatch vs the device-sync wait, per timed step, plus the process's
cumulative XLA compile count/seconds and persistent-cache hits/misses —
so a regression is attributed to a phase instead of guessed at.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BATCH = int(os.environ.get("BENCH_BATCH", "128"))
WARMUP = int(os.environ.get("BENCH_WARMUP", "2"))
# 60 steps/window: the dispatch+sync round trips of a window are a fixed
# cost, and 60 steps keep them a small part of it
STEPS = int(os.environ.get("BENCH_STEPS", "60"))
BULK = max(1, int(os.environ.get("BENCH_BULK", "10")))
REPEATS = max(1, int(os.environ.get("BENCH_REPEATS", "7")))
BASELINE_IPS = 45.52  # K80 ResNet-50 train, docs/how_to/perf.md:108-117
DTYPE = os.environ.get("BENCH_DTYPE", "bfloat16")


def device_fields(device):
    """The ``device`` every measurement row names, as JAX reports it."""
    import jax

    return {"platform": device.platform, "kind": device.device_kind,
            "count": len(jax.devices())}


def _bulk_attrib(mod):
    """Attribution of the compiled bulk step (one lower+compile covers
    fingerprint AND cost/memory): the hlo_fingerprint / cost_gflops /
    hbm_peak_bytes columns a regression bisect starts from.  Raises when
    the step was never bulked or yields no cost analysis."""
    from mxnet_tpu import perfdebug

    return perfdebug.analyze_signature(mod._last_bulk_sig)


def setup(num_layers=50, image_shape=(3, 224, 224), num_classes=1000,
          batch=BATCH, bulk=BULK, dtype=DTYPE, ctx=None):
    """Build the benchmarked Module + synthetic batches.

    Returns ``(mod, run, sync)`` where ``run(nsteps)`` dispatches that
    many full training steps in ``bulk``-sized scan bulks and ``sync()``
    is a cheap true device barrier.  Shared by ``bench.py`` itself,
    ``tools/perf/step_profile.py`` and ``chip_smoke.py`` so the profiled
    and smoke-tested step is EXACTLY the benchmarked step; the defaults
    are the benchmark's, the arguments exist for the CPU-sized tests.
    """
    # fwd+bwd+update as ONE XLA dispatch with donated param buffers
    os.environ.setdefault("MXNET_FUSE_TRAIN_STEP", "1")
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import io as mxio
    from mxnet_tpu import telemetry
    from mxnet_tpu.models import resnet

    # per-phase attribution of the measured step time (stack/dispatch
    # from Module.run_bulk, sync below, compile from the executor)
    telemetry.enable()

    if ctx is None:
        ctx = mx.context.measurement_context()
    data_shape = (batch,) + tuple(image_shape)

    net = resnet.get_symbol(num_classes=num_classes, num_layers=num_layers,
                            image_shape=tuple(image_shape))
    rs = np.random.RandomState(0)
    batches = [mxio.DataBatch(
        data=[mx.nd.array(rs.rand(*data_shape).astype(np.float32),
                          ctx=ctx, dtype=dtype)],
        label=[mx.nd.array(rs.randint(0, num_classes, batch)
                           .astype(np.float32), ctx=ctx)])
        for _ in range(bulk)]

    mod = mx.mod.Module(net, context=ctx)
    mod.bind(data_shapes=[("data", data_shape)],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                   magnitude=2))
    # bf16 params/activations; BatchNorm stats stay f32 inside the op.
    # Module has no dtype argument: this in-place cast after init_params
    # is the only bf16 training path there is
    if dtype != "float32":
        for n, a in mod._exec.arg_dict.items():
            if n not in ("softmax_label",):
                a._jx = a._jx.astype(dtype)
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9, "wd": 1e-4})

    def run(nsteps):
        done = 0
        while done < nsteps:
            mod.run_bulk(batches[:min(bulk, nsteps - done)])
            done += min(bulk, nsteps - done)

    def sync():
        # a 1-element host read of a just-updated param is the cheap TRUE
        # device barrier (reading the whole buffer would copy MBs to the
        # host); the final step's param update transitively depends on
        # every prior step
        with telemetry.phase("sync", family="bench"):
            return np.asarray(
                mod._exec.arg_dict["conv0_weight"]._jx.reshape(-1)[:1])

    return mod, run, sync


def main():
    mod, run, sync = setup()

    run(WARMUP * BULK)
    sync()

    attrib = _bulk_attrib(mod)
    flops_per_img = attrib["flops"] / BATCH
    device = mod._exec._ctx.jax_device()

    from mxnet_tpu import compile_cache, perfdebug, telemetry

    def _phase_sums():
        sums = {}
        for fam in ("bulk", "bench"):
            for ph, (s, _n) in telemetry.phase_totals(fam).items():
                sums[ph] = s
        return sums

    phase_base = _phase_sums()
    times = []
    for _ in range(REPEATS):
        t0 = time.time()
        run(STEPS)
        sync()
        times.append(time.time() - t0)
    best = min(times)
    median = sorted(times)[len(times) // 2]
    phase_end = _phase_sums()
    timed_steps = REPEATS * STEPS
    breakdown = {
        "%s_ms_per_step" % ph: round(
            1e3 * (phase_end.get(ph, 0.0) - phase_base.get(ph, 0.0))
            / timed_steps, 3)
        for ph in ("stack", "dispatch", "sync")}
    breakdown["compile_count"] = int(
        telemetry.counter_total("xla.compile.count"))
    breakdown["compile_s"] = round(
        telemetry.counter_total("xla.compile.seconds"), 2)
    if compile_cache.enabled():
        # how much of this process's compile_s was persistent-cache loads
        cc = compile_cache.stats()
        breakdown["persistent_cache_hits"] = cc["hits"]
        breakdown["persistent_cache_misses"] = cc["misses"]
        breakdown["persistent_cache_saved_s"] = \
            cc["compile_time_saved_seconds"]

    ips = BATCH * STEPS / median
    tflops = ips * flops_per_img / 1e12
    row = {
        "metric": "resnet50_train_imgs_per_sec_b%d" % BATCH,
        "value": round(ips, 2),
        "unit": "images/sec",
        "vs_baseline": round(ips / BASELINE_IPS, 3),
        "tflops": round(tflops, 2),
        "flops_per_img": round(flops_per_img / 1e9, 3),
        "value_best": round(BATCH * STEPS / best, 2),
        "repeats": REPEATS,
        "phase_breakdown": breakdown,
        "device": device_fields(device),
        # perf-attribution columns (docs/observability.md): a regression
        # bisect starts from "did the executable change and did it get
        # bigger", not guesswork
        "hlo_fingerprint": attrib["fingerprint"],
        "cost_gflops": round(attrib["flops"] / 1e9, 3),
    }
    if attrib.get("hbm_peak_bytes"):
        row["hbm_peak_bytes"] = int(attrib["hbm_peak_bytes"])
    if device.platform == "tpu":
        peak_tflops = perfdebug.device_peak_tflops(device)
        row["mfu_pct"] = round(100.0 * tflops / peak_tflops, 2)
        row["peak_tflops"] = peak_tflops
    print(json.dumps(row))


if __name__ == "__main__":
    main()
