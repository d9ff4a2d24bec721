#!/usr/bin/env python
"""Score a saved checkpoint on a validation set.

Reference: ``example/image-classification/score.py`` (loads
``prefix-symbol.json`` + ``prefix-%04d.params`` and runs ``mod.score``).
"""

import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from common import data  # noqa: E402

import mxnet_tpu as mx  # noqa: E402


def score(model_prefix, epoch, val_iter, metrics, batch_size):
    sym, arg_params, aux_params = mx.model.load_checkpoint(model_prefix,
                                                           epoch)
    ctx = mx.tpu() if mx.num_tpus() > 0 else mx.cpu()
    logging.info("scoring on %s (%s)", ctx, ctx.jax_device().device_kind)
    mod = mx.mod.Module(symbol=sym, context=ctx)
    mod.bind(for_training=False, data_shapes=val_iter.provide_data,
             label_shapes=val_iter.provide_label)
    mod.set_params(arg_params, aux_params)
    return mod.score(val_iter, metrics)


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description="score a model")
    parser.add_argument("--model-prefix", type=str, required=True)
    parser.add_argument("--load-epoch", type=int, required=True)
    parser.add_argument("--batch-size", type=int, default=64)
    parser.add_argument("--data-dir", type=str, default="data")
    parser.add_argument("--dataset", type=str, default="mnist",
                        choices=("mnist", "rec"))
    parser.add_argument("--image-shape", type=str, default="3,28,28")
    parser.add_argument("--num-classes", type=int, default=10)
    parser.add_argument("--num-examples", type=int, default=512)
    args = parser.parse_args()
    args.benchmark = 0

    kv = mx.kvstore.create("local")
    if args.dataset == "mnist":
        _, val = data.get_mnist_iter(args, kv)
    else:
        _, val = data.get_rec_iter(args, kv)
    metrics = [mx.metric.create("accuracy"),
               mx.metric.create("top_k_accuracy", top_k=5)]
    for name, value in score(args.model_prefix, args.load_epoch, val,
                             metrics, args.batch_size):
        print("%s: %f" % (name, value))
