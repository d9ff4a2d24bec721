#!/usr/bin/env python
"""Inference throughput sweep over the model zoo — the analog of the
reference's ``example/image-classification/benchmark_score.py`` whose
published numbers are the SURVEY §6 inference table
(``docs/how_to/perf.md:67-100``).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import models  # noqa: E402


def score(network, batch_size, image_shape=(3, 224, 224), num_batches=20,
          dtype="float32", return_mod=False, repeats=1, **net_kwargs):
    sym = models.get_symbol(network, num_classes=1000,
                            image_shape=image_shape, **net_kwargs)
    # the chip, or the CPU only when JAX_PLATFORMS=cpu names it
    ctx = mx.context.measurement_context()
    mod = mx.mod.Module(symbol=sym, context=ctx,
                        label_names=["softmax_label"])
    data_shape = (batch_size,) + tuple(image_shape)
    mod.bind(for_training=False, inputs_need_grad=False,
             data_shapes=[("data", data_shape)])
    mod.init_params(initializer=mx.init.Xavier(magnitude=2.0))
    if dtype != "float32":
        for n, a in mod._exec.arg_dict.items():
            a._jx = a._jx.astype(dtype)
    rs = np.random.RandomState(0)
    batch = mx.io.DataBatch(
        data=[mx.nd.array(rs.rand(*data_shape).astype(np.float32),
                          ctx=ctx, dtype=dtype)], label=[])

    # K forwards scanned inside one dispatch (Module.predict_bulk): the
    # honest throughput on an async backend — waiting on the last of K
    # *independent* dispatches lets the runtime overlap or dedupe them
    # and the clock lies by orders of magnitude
    bulk = [batch] * min(5, num_batches)

    def sync():
        np.asarray(mod._exec.outputs[0]._jx.reshape(-1)[:1])

    mod.predict_bulk(bulk)
    sync()
    # best-of-N timed windows (repeats>1): a short window is mostly its
    # fixed dispatch+sync cost
    best = float("inf")
    for _ in range(max(1, repeats)):
        tic = time.time()
        done = 0
        while done < num_batches:
            mod.predict_bulk(bulk)
            done += len(bulk)
        sync()
        best = min(best, time.time() - tic)
    ips = done * batch_size / best
    return (ips, mod) if return_mod else ips


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description="inference benchmark")
    parser.add_argument("--networks", type=str,
                        default="alexnet,vgg,inception-bn,inception-v3,"
                        "resnet,resnext")
    parser.add_argument("--batch-sizes", type=str, default="32")
    parser.add_argument("--num-layers", type=int, default=50,
                        help="for resnet/resnext")
    parser.add_argument("--dtype", type=str, default="float32")
    args = parser.parse_args()

    for net in args.networks.split(","):
        kw = {"num_layers": args.num_layers} \
            if net in ("resnet", "resnext") else {}
        for b in (int(x) for x in args.batch_sizes.split(",")):
            ips, mod = score(net, b, dtype=args.dtype, return_mod=True,
                             **kw)
            dev = mod._exec._ctx.jax_device()
            print("network: %s  batch: %d  dtype: %s  images/sec: %.1f  "
                  "device: %s (%s)"
                  % (net, b, args.dtype, ips, dev.platform,
                     dev.device_kind))
