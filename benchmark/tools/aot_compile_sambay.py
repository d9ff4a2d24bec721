"""The sandbox rehearsal for ``configs/phi-4-mini-flash-reasoning.json``:
the decode engine's own ``jit_step`` and ``jit_prefill`` programs, built by
``DecodeEngine`` over ``models/sambay.py`` at the configuration's widths,
compiled for a described ``v5e:2x2`` chip without the chip, with
``memory_analysis()``, the layout the compiler keeps each kind of slot
state in, and any copy of an array the size of one.  Nothing runs.

    JAX_PLATFORMS=cpu python benchmark/tools/aot_compile_sambay.py [slots [max_len]] [step|prefill ...]
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

    from benchmark.families import sambay_engine as family
    from benchmark.reference import sambay_engine as ref
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
    engine = Shapes(family.model_of(config), {},
                    slots=config["engine"]["slots"],
                    prefill_buckets=config["engine"]["prefill_buckets"],
                    autostart=False)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.eval_shape(
        lambda: ref.init_weights(config, 0, jax.devices()[0]))
    return (engine,) + family.step_shapes(engine, params, sds) + (sds,)


def prefill_shapes(params, state, bucket, sds):
    """The arguments of ``engine._prefill_fns[bucket]`` as shapes."""
    import jax.numpy as jnp

    return (params, state, sds((bucket,), jnp.int32), sds((), jnp.int32),
            sds((), jnp.int32), sds((), jnp.int32), sds((), jnp.float32),
            sds((), jnp.uint32), sds((), jnp.bool_))


def state_shapes(state):
    """The distinct shapes of the slot state's big arrays."""
    return sorted({a.shape for side in state[:2] for a in side
                   if a.ndim >= 3})


def cache_copies(text, state):
    """Copies of an array the size of a slot-state array in compiled
    text."""
    found = []
    for shape in state_shapes(state):
        found += re.findall(r"= \w+\[%s\]\{[^}]*\} copy\(.*"
                            % ",".join(map(str, shape)), text)
    return found


def main():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import harness
    from mxnet_tpu.ops import registry

    config = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs", "phi-4-mini-flash-reasoning.json"))
    numbers = [int(a) for a in sys.argv[1:] if a.isdigit()]
    which = [a for a in sys.argv[1:] if not a.isdigit()] \
        or ["step", "prefill"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    engine, params, state, keep, extra, sds = engine_programs(
        config, SingleDeviceSharding(topo.devices[0]), *numbers)
    s = engine.slots
    todo = []
    if "step" in which:
        todo.append(("step", engine._step_fn, (params, state, keep, extra)))
    if "prefill" in which:
        todo += [("prefill %d" % b, engine._prefill_fns[b],
                  prefill_shapes(params, state, b, sds))
                 for b in engine.prefill_buckets]
    # the trace is bound for the chip: the kernels' dispatch rules ask
    registry.trace_device.set("tpu")
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
        print("  kernels (tpu_custom_call): %d"
              % text.count("tpu_custom_call"))
        for shape in state_shapes(state):
            layouts = sorted(set(re.findall(
                r"\w+\[%s\]\{[^}]*\}" % ",".join(map(str, shape)), text)))
            print("  slot state %s lives as: %s" % (shape, layouts[:4]))
        copies = cache_copies(text, state)
        print("  copies of a slot-state-sized array: %d%s" % (
            len(copies), (" first: " + copies[0][:160]) if copies else ""),
            flush=True)


if __name__ == "__main__":
    main()
