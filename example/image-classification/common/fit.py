"""The shared ``Module.fit`` training harness for all image-classification
examples.

Reference: ``example/image-classification/common/fit.py`` — lr-factor
scheduling (:6-23), checkpoint resume (:24-35), per-rank checkpoint
prefixes, ``--kv-store device`` default, ``--test-io`` IO-throughput mode,
``--benchmark`` synthetic-data mode.  TPU notes: ``--kv-store device``
maps to an in-XLA allreduce over the chip mesh.  Training is float32:
``Module`` has no dtype argument, so ``--dtype bfloat16`` is refused
with the path that is missing (``chip_smoke.py``'s bulk phase casts the
bound arrays in place after ``init_params``; nothing public does).
"""

import argparse
import logging
import os
import time

import mxnet_tpu as mx


def _get_lr_scheduler(args, kv):
    """reference fit.py:6-23 — FactorScheduler at epoch boundaries."""
    if not args.lr_step_epochs:
        return args.lr, None
    epoch_size = max(args.num_examples // args.batch_size // kv.num_workers, 1)
    step_epochs = [int(x) for x in args.lr_step_epochs.split(",")]
    lr = args.lr
    begin = args.load_epoch or 0
    for s in step_epochs:
        if begin >= s:
            lr *= args.lr_factor
    if lr != args.lr:
        logging.info("Adjust learning rate to %e for epoch %d", lr, begin)
    steps = [epoch_size * (x - begin) for x in step_epochs
             if x - begin > 0]
    if not steps:
        return lr, None
    return lr, mx.lr_scheduler.MultiFactorScheduler(step=steps,
                                                    factor=args.lr_factor)


def _load_model(args, rank=0):
    """reference fit.py:24-35 — resume from --model-prefix + --load-epoch."""
    if args.load_epoch is None or args.model_prefix is None:
        return None, None, None
    model_prefix = args.model_prefix
    if rank > 0 and os.path.exists("%s-%d-symbol.json"
                                   % (model_prefix, rank)):
        model_prefix += "-%d" % rank
    sym, arg_params, aux_params = mx.model.load_checkpoint(model_prefix,
                                                           args.load_epoch)
    logging.info("Loaded model %s_%04d.params", model_prefix,
                 args.load_epoch)
    return sym, arg_params, aux_params


def _save_model(args, rank=0):
    if args.model_prefix is None:
        return None
    dst_dir = os.path.dirname(args.model_prefix)
    if dst_dir and not os.path.isdir(dst_dir):
        os.makedirs(dst_dir, exist_ok=True)
    prefix = args.model_prefix if rank == 0 \
        else "%s-%d" % (args.model_prefix, rank)
    return mx.callback.do_checkpoint(prefix)


def add_fit_args(parser):
    """reference fit.py add_fit_args."""
    train = parser.add_argument_group("Training")
    train.add_argument("--network", type=str, default="lenet")
    train.add_argument("--num-layers", type=int, default=50)
    train.add_argument("--gpus", type=str, default=None,
                       help="unused on TPU; kept for CLI parity")
    train.add_argument("--kv-store", type=str, default="device")
    train.add_argument("--num-epochs", type=int, default=2)
    train.add_argument("--lr", type=float, default=0.05)
    train.add_argument("--lr-factor", type=float, default=0.1)
    train.add_argument("--lr-step-epochs", type=str, default=None)
    train.add_argument("--optimizer", type=str, default="sgd")
    train.add_argument("--mom", type=float, default=0.9)
    train.add_argument("--wd", type=float, default=1e-4)
    train.add_argument("--batch-size", type=int, default=64)
    train.add_argument("--disp-batches", type=int, default=20)
    train.add_argument("--model-prefix", type=str, default=None)
    train.add_argument("--load-epoch", type=int, default=None)
    train.add_argument("--top-k", type=int, default=0)
    train.add_argument("--test-io", type=int, default=0,
                       help="1 = measure input-pipeline throughput only")
    train.add_argument("--dtype", type=str, default="float32",
                       choices=("float32", "bfloat16"),
                       help="float32 only: bfloat16 is refused, Module "
                            "cannot bind or initialise bf16 parameters")
    train.add_argument("--monitor", dest="monitor", type=int, default=0)
    return train


def fit(args, network, data_loader, **kwargs):
    """reference fit.py fit() — the full train flow."""
    if args.dtype != "float32":
        raise NotImplementedError(
            "--dtype %s: Module.bind/init_params create float32 "
            "parameters whatever the iterator's dtype, and there is no "
            "public cast (chip_smoke.py's bulk phase rewrites "
            "Executor.arg_dict in place)"
            % args.dtype)
    kv = mx.kvstore.create(args.kv_store)
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)-15s Node[" + str(kv.rank)
                        + "] %(message)s")
    logging.info("start with arguments %s", args)

    train, val = data_loader(args, kv)
    if args.test_io:
        # IO-throughput-only mode (reference fit.py --test-io)
        tic = time.time()
        for i, batch in enumerate(train):
            for j in batch.data:
                j.wait_to_read()
            if (i + 1) % args.disp_batches == 0:
                logging.info("Batch [%d]\tSpeed: %.2f samples/sec", i,
                             args.disp_batches * args.batch_size
                             / (time.time() - tic))
                tic = time.time()
        return

    sym, arg_params, aux_params = _load_model(args, kv.rank)
    if sym is not None:
        network = sym
    # callers (fine-tune.py) may seed params explicitly
    arg_params = kwargs.pop("arg_params", arg_params)
    aux_params = kwargs.pop("aux_params", aux_params)

    lr, lr_scheduler = _get_lr_scheduler(args, kv)
    optimizer_params = {"learning_rate": lr, "wd": args.wd,
                        "lr_scheduler": lr_scheduler}
    if args.optimizer in ("sgd", "nag"):
        optimizer_params["momentum"] = args.mom

    checkpoint = _save_model(args, kv.rank)
    ctx = mx.tpu() if mx.num_tpus() > 0 else mx.cpu()
    logging.info("training on %s (%s)", ctx, ctx.jax_device().device_kind)
    model = mx.mod.Module(symbol=network, context=ctx)

    # callers (chip_smoke.py) may add metrics and per-batch callbacks
    eval_metrics = ["accuracy"] + list(kwargs.pop("extra_metrics", ()))
    batch_end_callbacks = [
        mx.callback.Speedometer(args.batch_size, args.disp_batches)] \
        + list(kwargs.pop("extra_batch_end_callbacks", ()))
    if args.top_k > 0:
        eval_metrics.append(mx.metric.create("top_k_accuracy",
                                             top_k=args.top_k))
    monitor = mx.mon.Monitor(args.disp_batches, pattern=".*") \
        if args.monitor > 0 else None

    initializer = mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                 magnitude=2)
    model.fit(train,
              begin_epoch=args.load_epoch if args.load_epoch else 0,
              num_epoch=args.num_epochs,
              eval_data=val,
              eval_metric=eval_metrics,
              kvstore=kv,
              optimizer=args.optimizer,
              optimizer_params=optimizer_params,
              initializer=initializer,
              arg_params=arg_params,
              aux_params=aux_params,
              batch_end_callback=batch_end_callbacks,
              epoch_end_callback=checkpoint,
              allow_missing=True,
              monitor=monitor,
              **kwargs)
    return model
