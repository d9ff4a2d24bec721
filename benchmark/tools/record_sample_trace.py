"""Record the small trace ``trace_reduce.py`` is checked on: a two-layer LM
behind ``DecodeEngine`` serving three sessions, traced for about a second.
Writes the ``.xplane.pb`` under ``chiprun_out/sample_trace/`` and prints the
planes, lines and first events it holds.  Run on the chip:
``chiprun -- python benchmark/tools/record_sample_trace.py``."""

import glob
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main():
    import jax
    import numpy as np
    from jax.profiler import ProfileData

    from mxnet_tpu import serving
    from mxnet_tpu.models import transformer_lm as tlm

    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, len(jax.devices()),
          sorted((dev.memory_stats() or {}).keys()), flush=True)
    cfg = tlm.LMConfig(512, 128, 4, 2, 512, 128, eos_id=512)
    params = jax.device_put(tlm.init_params(cfg, seed=0), dev)
    pool = serving.lm_pool(cfg, params, n_replicas=1, devices=[dev],
                           name="sample", engine_opts={
                               "slots": 4, "prefill_buckets": (16, 64)})
    out = os.path.join(ROOT, "chiprun_out", "sample_trace")
    shutil.rmtree(out, ignore_errors=True)
    rs = np.random.RandomState(0)
    pool.generate(rs.randint(0, 512, 8), max_new_tokens=4).result(60)
    jax.profiler.start_trace(out)
    t0 = time.monotonic()
    with jax.profiler.TraceAnnotation("bench.sending"):
        sessions = [pool.generate(rs.randint(0, 512, n), max_new_tokens=40)
                    for n in (8, 12, 40)]
    with jax.profiler.TraceAnnotation("bench.waiting"):
        for s in sessions:
            s.result(60)
    time.sleep(0.05)
    jax.profiler.stop_trace()
    print("traced_s", time.monotonic() - t0, flush=True)
    pool.close(drain=False)
    (path,) = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    print("xplane", path, os.path.getsize(path))
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events),
                  [(e.name[:60], e.start_ns, e.duration_ns)
                   for e in events[:4]])


if __name__ == "__main__":
    main()
