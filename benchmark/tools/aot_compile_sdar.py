"""The sandbox rehearsal for a decode configuration, ``configs/
sdar-30b-a3b-chat.json`` unless another is named: the decode engine's own
``jit_step`` (for this model a pass over ``(slots, 4)`` rows) and
``jit_prefill`` programs, built by ``DecodeEngine`` over the model the
configuration's family makes (``families/<family>.py`` ``model_of`` and
``step_shapes``, ``reference/<family>.py`` ``init_weights``) at the
configuration's widths, compiled for a described ``v5e:2x2`` chip without
the chip, with ``memory_analysis()``, what is held beside a program
(weights, cache), the layout the compiler keeps each kind of cache in, and
any copy of an array the size of one.  Nothing runs.

The fifth tool of its kind and the first that asks the configuration for
its family: ``aot_compile_smallthinker.py`` and ``aot_compile_deepseek_v2.py``
name theirs in the text and are not this PR's to edit; this one runs their
configurations too.

    JAX_PLATFORMS=cpu python benchmark/tools/aot_compile_sdar.py [config] [slots] [step|prefill|<bucket> ...]
"""

import importlib
import math
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.tools.aot_compile_sambay import (  # noqa: E402 — sets the environment of a compile-only process as it is imported
    cache_copies, prefill_shapes, state_shapes)

CONFIG = "sdar-30b-a3b-chat"


def engine_programs(config, one_chip, slots=None):
    """(engine, params, state, keep, extra, sds): an engine that builds its
    programs and neither state nor warm-up, so nothing of the real size is
    ever allocated here, and its step's arguments as shapes pinned to
    ``one_chip``."""
    import jax

    from mxnet_tpu.serving import DecodeEngine

    family = importlib.import_module("benchmark.families."
                                     + config["family"])
    ref = importlib.import_module("benchmark.reference." + config["family"])

    class Shapes(DecodeEngine):
        def _fresh_state(self):
            return None

        def _warm(self, state):
            return state

    config = dict(config, engine=dict(config["engine"]))
    if slots:
        config["engine"]["slots"] = slots
    engine = Shapes(family.model_of(config), {},
                    slots=config["engine"]["slots"],
                    prefill_buckets=config["engine"]["prefill_buckets"],
                    autostart=False)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.eval_shape(
        lambda: ref.init_weights(config, 0, jax.devices()[0]))
    return (engine,) + family.step_shapes(engine, params, sds) + (sds,)


def main():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import harness
    from mxnet_tpu.ops import registry

    words = sys.argv[1:]
    named = [a for a in words if os.path.exists(os.path.join(
        ROOT, "benchmark", "configs", a + ".json"))]
    config = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs", (named or [CONFIG])[0] + ".json"))
    words = [a for a in words if a not in named]
    # a number is a bucket if the configuration has it, else the slots
    numbers = [int(a) for a in words if a.isdigit()]
    buckets = [n for n in numbers if n in config["engine"]["prefill_buckets"]]
    slots = next((n for n in numbers if n not in buckets), None)
    which = [a for a in words if not a.isdigit()] \
        or ([] if buckets else ["step", "prefill"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    engine, params, state, keep, extra, sds = engine_programs(
        config, SingleDeviceSharding(topo.devices[0]), slots)
    s = engine.slots
    todo = []
    if "step" in which:
        todo.append(("step", engine._step_fn, (params, state, keep, extra)))
    todo += [("prefill %d" % b, engine._prefill_fns[b],
              prefill_shapes(params, state, b, sds))
             for b in engine.prefill_buckets
             if "prefill" in which or b in buckets]
    # the trace is bound for the chip: the kernels' dispatch rules ask
    registry.trace_device.set("tpu")
    for what, fn, shapes in todo:
        t0 = time.time()
        compiled = fn.lower(*shapes).compile()
        ma = compiled.memory_analysis()
        total = ma.argument_size_in_bytes + ma.output_size_in_bytes \
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes
        print("%s at %d slots: compiled in %.0f s; arguments %.3f GB "
              "(weights and cache held), outputs %.3f, aliased %.3f, "
              "temporaries %.3f, program total %.3f GB" % (
                  what, s, time.time() - t0,
                  ma.argument_size_in_bytes / 1e9,
                  ma.output_size_in_bytes / 1e9,
                  ma.alias_size_in_bytes / 1e9, ma.temp_size_in_bytes / 1e9,
                  total / 1e9), flush=True)
        text = compiled.as_text()
        print("  kernels (tpu_custom_call): %d; ragged products: %d"
              % (text.count("tpu_custom_call"), text.count("ragged-dot")))
        for shape in state_shapes(state):
            layouts = sorted(set(re.findall(
                r"\w+\[%s\]\{[^}]*\}" % ",".join(map(str, shape)), text)))
            print("  cache %s lives as: %s" % (shape, layouts[:4]))
        copies = cache_copies(text, state)
        print("  copies of a cache-sized array: %d%s" % (
            len(copies), (" first: " + copies[0][:160]) if copies else ""),
            flush=True)
        sizes = {"f32": 4, "bf16": 2, "s32": 4}
        big = sorted(((math.prod(map(int, dims.split(","))) * sizes[t], t,
                       dims) for t, dims in set(re.findall(
                           r"= (f32|bf16|s32)\[([\d,]+)\]", text))),
                     reverse=True)[:6]
        print("  largest arrays named in the program: %s" % ", ".join(
            "%s[%s] %.2f GB" % (t, d, b / 1e9) for b, t, d in big),
            flush=True)


if __name__ == "__main__":
    main()
