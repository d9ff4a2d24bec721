"""The third sandbox rehearsal for ``configs/k-exaone-236b-a23b.json``:
the decode engine's own ``jit_step`` and ``jit_prefill`` programs, built by
``DecodeEngine`` over ``models/exaone_moe.py`` at the configuration's
widths, compiled for a described ``v5e:2x2`` chip without the chip, with
``memory_analysis()``, the layout the compiler keeps each kind of cache
array in, and any copy of a cache-sized array.  Nothing runs.

    JAX_PLATFORMS=cpu python benchmark/tools/aot_compile_moe.py [slots [max_len]]
"""

import os
import re
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def engine_programs(config, one_chip, slots=None, max_len=None):
    """(engine, params, state, keep, extra, sds): an engine that builds its
    programs and neither state nor warm-up, so nothing of the real size is
    ever allocated here, and its step's arguments as shapes pinned to
    ``one_chip``."""
    import jax
    import jax.numpy as jnp

    from benchmark.families import exaone_moe_engine as family
    from benchmark.reference import exaone_moe_engine as ref
    from mxnet_tpu.models import exaone_moe as xm
    from mxnet_tpu.serving import DecodeEngine

    class Shapes(DecodeEngine):
        def _fresh_state(self):
            return None

        def _warm(self, state):
            return state

    config = dict(config, engine=dict(config["engine"]))
    if slots:
        config["engine"]["slots"] = slots
    if max_len:
        config["engine"]["max_len"] = max_len
    cfg = family.model_config(xm, ref.sizes(config))
    model = xm.ExaoneMoE(cfg, jnp.dtype(config["precision"]["kv_cache"]))
    engine = Shapes(model, {}, slots=config["engine"]["slots"],
                    prefill_buckets=config["engine"]["prefill_buckets"],
                    autostart=False)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.eval_shape(lambda: xm.init_params(cfg, 0, jnp.dtype(
        config["precision"]["weights"])))
    return (engine,) + family.step_shapes(engine, params, sds) + (sds,)


def cache_copies(text, state):
    """Copies of an array the size of a cache array in compiled text."""
    found = []
    for shape in {a.shape for a in state[0]}:
        found += re.findall(
            r"= \w+\[%d,%d,%d,%d\]\{[^}]*\} copy\(.*" % shape, text)
    return found


def main():
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import harness

    config = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs", "k-exaone-236b-a23b.json"))
    args = [int(a) for a in sys.argv[1:]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    engine, params, state, keep, extra, sds = engine_programs(
        config, SingleDeviceSharding(topo.devices[0]), *args)
    s = engine.slots
    todo = [("step", engine._step_fn, (params, state, keep, extra))]
    for b in engine.prefill_buckets:
        todo.append(("prefill %d" % b, engine._prefill_fns[b], (
            params, state, sds((b,), jnp.int32), sds((), jnp.int32),
            sds((), jnp.int32), sds((), jnp.int32), sds((), jnp.float32),
            sds((), jnp.uint32), sds((), jnp.bool_))))
    for what, fn, shapes in todo:
        t0 = time.time()
        compiled = fn.lower(*shapes).compile()
        ma = compiled.memory_analysis()
        total = ma.argument_size_in_bytes + ma.output_size_in_bytes \
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes
        print("%s at %d slots: compiled in %.0f s; arguments %.3f GB, "
              "outputs %.3f, aliased %.3f, temporaries %.3f, program "
              "total %.3f GB" % (
                  what, s, time.time() - t0,
                  ma.argument_size_in_bytes / 1e9,
                  ma.output_size_in_bytes / 1e9,
                  ma.alias_size_in_bytes / 1e9, ma.temp_size_in_bytes / 1e9,
                  total / 1e9), flush=True)
        text = compiled.as_text()
        for shape in sorted({a.shape for a in state[0]}):
            layouts = sorted(set(re.findall(
                r"bf16\[%d,%d,%d,%d\]\{[^}]*\}" % shape, text)))
            print("  cache %s lives as: %s" % (shape, layouts[:4]))
        copies = cache_copies(text, state)
        print("  copies of a cache-sized array: %d%s" % (
            len(copies), (" first: " + copies[0][:160]) if copies else ""),
            flush=True)


if __name__ == "__main__":
    main()
