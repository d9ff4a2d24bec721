#!/usr/bin/env python3
"""Fingerprints (``perfdebug.fingerprint_text``) of the decode engine's
``jit_step`` and every ``jit_prefill`` bucket as they lower for each decode
configuration of the benchmark, at the configuration's own sizes: nothing
is allocated and nothing runs.  A change to the engine or to the model
protocol that is not meant to change a model's programs shows here as the
same digests before and after.  With ``--tpu`` the programs are traced as
they are for the chip (``ops.registry.trace_device``) and lowered for it, so
the Pallas kernels are part of the text: each kernel's body is read back
and printed without the paths and lines of its call stack, so two trees, or
one before and after a move of code, compare by what the kernels do;
without, as the CPU runs them.  With ``--tiny`` the configurations are
those of ``tests/benchmark/tiny/`` (a few slots, buckets of tens: seconds
where the benchmark's own take minutes), which is how a PR that adds a
tail to the engine shows that the small engines of the models it does not
touch lower to the parent's text: run it in both trees and ``diff``.

    JAX_PLATFORMS=cpu python tools/perf/program_fingerprints.py [--tpu] [--tiny] [config ...]
"""

import base64
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def programs(config):
    """(name, jitted function, argument shapes) of every program the
    engine builds for ``config``."""
    import jax
    import jax.numpy as jnp

    from benchmark import harness
    from mxnet_tpu.serving import DecodeEngine

    class Unbuilt(DecodeEngine):
        def _fresh_state(self):
            return None

        def _warm(self, state):
            return state

    family = harness.find("families", config["family"])
    ref = harness.find("reference", config["family"])
    eng = config["engine"]
    engine = Unbuilt(_model(config, family, ref), {}, slots=eng["slots"],
                     prefill_buckets=eng["prefill_buckets"],
                     autostart=False)
    params = jax.eval_shape(
        lambda: ref.init_weights(config, 0, jax.devices()[0]))
    sds = jax.ShapeDtypeStruct
    shapes = family.step_shapes(engine, params, sds) \
        if hasattr(family, "step_shapes") else _gpt2_shapes(engine, params)
    out = [("jit_step", engine._step_fn, shapes)]
    for b in engine.prefill_buckets:
        out.append(("jit_prefill %d" % b, engine._prefill_fns[b], (
            shapes[0], shapes[1], sds((b,), jnp.int32), sds((), jnp.int32),
            sds((), jnp.int32), sds((), jnp.int32), sds((), jnp.float32),
            sds((), jnp.uint32), sds((), jnp.bool_))))
    return out


def _model(config, family, ref):
    """The model object each family hands the engine."""
    import jax.numpy as jnp

    if hasattr(family, "model_of"):
        return family.model_of(config)
    if config["family"] == "exaone_moe_engine":
        from mxnet_tpu.models import exaone_moe as xm

        return xm.ExaoneMoE(family.model_config(xm, ref.sizes(config)),
                            jnp.dtype(config["precision"]["kv_cache"]))
    from mxnet_tpu.models import transformer_lm as tlm

    return tlm.LMConfig(*ref.sizes(config), eos_id=ref.sizes(config)[0])


def _gpt2_shapes(engine, params):
    """``families/decode_engine.py`` ``scratch_bytes`` writes these out by
    hand: the same shapes."""
    import jax
    import jax.numpy as jnp

    sds = jax.ShapeDtypeStruct
    cfg, s = engine.cfg, engine.slots
    kv = tuple(sds((s, cfg.max_len, cfg.heads, cfg.embed // cfg.heads),
                   jnp.float32) for _ in range(cfg.layers))
    state = (kv, kv, sds((s,), jnp.int32), sds((s,), jnp.int32),
             sds((s,), jnp.int32), sds((s,), jnp.bool_),
             sds((s,), jnp.float32), sds((s,), jnp.uint32))
    params = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), params)
    return params, state, sds((s,), jnp.bool_)


_BODY_RE = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def kernels_without_locations(text):
    """``text`` with every Pallas kernel's serialized body replaced by its
    operations as text, locations left out."""
    from jax._src.interpreters import mlir
    from jaxlib.mlir import ir

    def plain(match):
        with mlir.make_ir_context() as context:
            context.allow_unregistered_dialects = True
            return ir.Module.parse(base64.b64decode(match.group(1))) \
                .operation.get_asm(enable_debug_info=False)

    return _BODY_RE.sub(plain, text)


def main():
    import jax

    from benchmark import harness
    from mxnet_tpu import perfdebug
    from mxnet_tpu.ops import registry

    names = [a for a in sys.argv[1:] if a not in ("--tpu", "--tiny")]
    configs = os.path.join(ROOT, "tests", "benchmark", "tiny") \
        if "--tiny" in sys.argv else os.path.join(ROOT, "benchmark",
                                                  "configs")
    platform = "cpu"
    if "--tpu" in sys.argv:
        platform = "tpu"
        registry.trace_device.set(platform)
    names = names or sorted(f[:-5] for f in os.listdir(configs))
    for name in names:
        config = harness.load_json(os.path.join(configs, name + ".json"))
        if "engine" not in config:
            continue
        for what, fn, shapes in programs(config):
            jax.clear_caches()
            text = fn.trace(*shapes).lower(
                lowering_platforms=(platform,)).as_text()
            if platform == "tpu":
                text = kernels_without_locations(text)
            print("%s %s %s" % (name, what,
                                perfdebug.fingerprint_text(text)),
                  flush=True)


if __name__ == "__main__":
    main()
