"""The third sandbox rehearsal: compile a cell's programs at their real
size for a described ``v5e:2x2`` chip, without the chip, and print
``memory_analysis()``.  Nothing runs; a program the chip's compiler would
refuse (too large for the device's memory, a kernel it cannot lower) is
refused here, at no chip time.

    JAX_PLATFORMS=cpu python benchmark/tools/aot_compile.py decode 8 12 13
    JAX_PLATFORMS=cpu python benchmark/tools/aot_compile.py fit 256

``decode <slots>...``: the decode step (and the largest prefill) of
``configs/gpt2-large.json`` at each slot count: what ``slots_analysis``
in that file records.  ``fit <batch>``: the ``train`` program
``Module`` binds for ``configs/resnet50.json`` at that batch."""

import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _report(what, t0, ma):
    total = ma.argument_size_in_bytes + ma.output_size_in_bytes \
        - ma.alias_size_in_bytes + ma.temp_size_in_bytes
    print("%s: compiled in %.0f s; arguments %.3f GB, outputs %.3f, "
          "aliased %.3f, temporaries %.3f, program total %.3f GB "
          "(%.3f GiB)" % (what, time.time() - t0,
                          ma.argument_size_in_bytes / 1e9,
                          ma.output_size_in_bytes / 1e9,
                          ma.alias_size_in_bytes / 1e9,
                          ma.temp_size_in_bytes / 1e9, total / 1e9,
                          total / 2 ** 30), flush=True)


def decode(one_chip, slot_counts):
    import jax
    import jax.numpy as jnp

    from benchmark import harness
    from benchmark.reference import decode_engine as ref
    from mxnet_tpu.models import transformer_lm as tlm

    config = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs", "gpt2-large.json"))
    vocab, embed, heads, layers, ffn, max_len = ref.sizes(config)
    cfg = tlm.LMConfig(vocab, embed, heads, layers, ffn, max_len, vocab)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(ref._init, ref.sizes(config), jax.random.PRNGKey(0)))

    def step(params, ck, cv, last, lengths):
        return tlm.decode_step_math(cfg, params, ck, cv, last, lengths)

    def prefill(params, ck, cv, tokens, length, slot):
        last, ks, vs = tlm.prefill_kv(cfg, params, tokens, length)
        put = jax.lax.dynamic_update_slice
        return (last,
                tuple(put(c, k[None], (slot, 0, 0, 0))
                      for c, k in zip(ck, ks)),
                tuple(put(c, v[None], (slot, 0, 0, 0))
                      for c, v in zip(cv, vs)))

    bucket = max(config["engine"]["prefill_buckets"])
    for slots in slot_counts:
        row = (slots, max_len, heads, embed // heads)
        ck = tuple(sds(row) for _ in range(layers))
        cv = tuple(sds(row) for _ in range(layers))
        t0 = time.time()
        c = jax.jit(step, donate_argnums=(1, 2)).lower(
            params, ck, cv, sds((slots,), jnp.int32),
            sds((slots,), jnp.int32)).compile()
        _report("decode step, %d slots" % slots, t0, c.memory_analysis())
        t0 = time.time()
        c = jax.jit(prefill, donate_argnums=(1, 2)).lower(
            params, ck, cv, sds((bucket,), jnp.int32), sds((), jnp.int32),
            sds((), jnp.int32)).compile()
        _report("prefill %d, %d slots" % (bucket, slots), t0,
                c.memory_analysis())


def fit(one_chip, batch):
    import jax

    import mxnet_tpu as mx
    from benchmark import harness
    from mxnet_tpu.models import resnet

    config = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs", "resnet50.json"))
    sym = resnet.resnet(
        units=config["units"], num_stages=len(config["units"]),
        filter_list=config["filter_list"],
        num_classes=config["num_classes"],
        image_shape=tuple(config["image_shape"]),
        bottle_neck=config["bottle_neck"])
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", (batch,) + tuple(config["image_shape"]))],
             label_shapes=[("softmax_label", (batch,))], for_training=True)
    ex = mod._exec

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    rng = ex.next_rng()
    t0 = time.time()
    c = ex._get_fn("train").lower(
        [sds(ex.arg_dict[n]._jx) for n in ex.arg_names],
        [sds(a._jx) for a in ex.aux_arrays], sds(rng)).compile()
    _report("train program, batch %d" % batch, t0, c.memory_analysis())


def main():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    what, sizes = sys.argv[1], [int(a) for a in sys.argv[2:]]
    if what == "decode":
        decode(one_chip, sizes)
    elif what == "fit":
        for batch in sizes:
            fit(one_chip, batch)
    else:
        raise SystemExit(__doc__)


if __name__ == "__main__":
    main()
