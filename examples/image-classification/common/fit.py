"""Shared training driver for the image-classification examples.

Reference: ``example/image-classification/common/fit.py`` — the one place
every train_* script funnels through: kvstore creation, lr scheduling,
checkpointing, Speedometer, Module.fit."""
from __future__ import annotations

import logging
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "..", ".."))
import mxnet_tpu as mx  # noqa: E402


def _get_lr_scheduler(args, kv):
    if not args.lr_factor or args.lr_factor >= 1:
        return (args.lr, None)
    epoch_size = args.num_examples // args.batch_size
    if "dist" in args.kv_store:
        epoch_size //= kv.num_workers
    begin_epoch = args.load_epoch if args.load_epoch else 0
    step_epochs = [int(l) for l in args.lr_step_epochs.split(",")]
    lr = args.lr
    for s in step_epochs:
        if begin_epoch >= s:
            lr *= args.lr_factor
    if lr != args.lr:
        logging.info("Adjust learning rate to %e for epoch %d",
                     lr, begin_epoch)
    steps = [epoch_size * (x - begin_epoch) for x in step_epochs
             if x - begin_epoch > 0]
    if not steps:  # resumed at/after the last step: lr already final
        return (lr, None)
    return (lr, mx.lr_scheduler.MultiFactorScheduler(step=steps,
                                                     factor=args.lr_factor))


def _load_model(args, rank=0):
    if args.load_epoch is None or not args.model_prefix:
        return (None, None, None)
    model_prefix = args.model_prefix
    sym, arg_params, aux_params = mx.model.load_checkpoint(
        model_prefix, args.load_epoch)
    logging.info("Loaded model %s_%04d.params", model_prefix,
                 args.load_epoch)
    return (sym, arg_params, aux_params)


def _save_model(args, rank=0):
    if not args.model_prefix:
        return None
    dst_dir = os.path.dirname(args.model_prefix)
    if dst_dir and not os.path.isdir(dst_dir):
        os.makedirs(dst_dir, exist_ok=True)
    return mx.callback.do_checkpoint(
        args.model_prefix if rank == 0
        else "%s-%d" % (args.model_prefix, rank))


def add_fit_args(parser):
    """Reference fit.py add_fit_args: the shared training CLI."""
    train = parser.add_argument_group("Training")
    train.add_argument("--network", type=str, help="the neural network to use")
    train.add_argument("--num-layers", type=int,
                       help="number of layers, e.g. resnet depth")
    train.add_argument("--gpus", type=str,
                       help="devices, e.g. '0,1' (tpu cores here)")
    train.add_argument("--kv-store", type=str, default="device")
    train.add_argument("--num-epochs", type=int, default=100)
    train.add_argument("--lr", type=float, default=0.1)
    train.add_argument("--lr-factor", type=float, default=0.1)
    train.add_argument("--lr-step-epochs", type=str, default="30,60")
    train.add_argument("--optimizer", type=str, default="sgd")
    train.add_argument("--mom", type=float, default=0.9)
    train.add_argument("--wd", type=float, default=0.0001)
    train.add_argument("--batch-size", type=int, default=128)
    train.add_argument("--disp-batches", type=int, default=20)
    train.add_argument("--model-prefix", type=str)
    train.add_argument("--load-epoch", type=int)
    train.add_argument("--top-k", type=int, default=0)
    train.add_argument("--test-io", type=int, default=0,
                       help="test data pipeline throughput only")
    train.add_argument("--dtype", type=str, default="float32",
                       choices=["float32", "bfloat16"],
                       help="compute dtype (bfloat16 = MXU fast path)")
    return train


def fit(args, network, data_loader, arg_params=None, aux_params=None,
        **kwargs):
    """Train `network` on the loader (reference fit.py fit()).

    ``arg_params``/``aux_params`` seed the parameters when no
    ``--load-epoch`` checkpoint overrides them (the fine-tune entry
    point passes the surgically transferred backbone this way)."""
    mx.base.use_compile_cache()
    if getattr(args, "benchmark", 0):
        # --gpus picks tpu(i), which resolves to host devices where
        # there is no accelerator: a benchmark must not pass for that
        mx.context.require_tpu("--benchmark 1")
    kv = mx.create_kvstore(args.kv_store)
    head = "%(asctime)-15s Node[" + str(kv.rank) + "] %(message)s"
    logging.basicConfig(level=logging.INFO, format=head)
    logging.info("start with arguments %s", args)

    (train, val) = data_loader(args, kv)
    if args.test_io:
        tic = time.time()
        for i, batch in enumerate(train):
            for j in batch.data:
                j.wait_to_read()
            if (i + 1) % args.disp_batches == 0:
                logging.info("Batch [%d]\tSpeed: %.2f samples/sec", i,
                             args.disp_batches * args.batch_size /
                             (time.time() - tic))
                tic = time.time()
        return

    sym, ck_args, ck_auxs = _load_model(args, kv.rank)
    if sym is not None:
        network = sym
    if ck_args is not None:
        arg_params, aux_params = ck_args, ck_auxs

    devs = mx.cpu() if args.gpus is None or args.gpus == "" else [
        mx.tpu(int(i)) for i in args.gpus.split(",")]

    lr, lr_scheduler = _get_lr_scheduler(args, kv)

    model = mx.Module(context=devs, symbol=network,
                      compute_dtype=("bfloat16" if args.dtype == "bfloat16"
                                     else None))

    optimizer_params = {
        "learning_rate": lr,
        "momentum": args.mom,
        "wd": args.wd,
        "lr_scheduler": lr_scheduler}
    if args.optimizer in ("adam", "adagrad", "rmsprop", "adadelta", "ftrl"):
        optimizer_params.pop("momentum")

    checkpoint = _save_model(args, kv.rank)

    initializer = mx.initializer.Xavier(rnd_type="gaussian",
                                        factor_type="in", magnitude=2)
    eval_metrics = ["accuracy"]
    if args.top_k > 0:
        eval_metrics.append(mx.metric.create("top_k_accuracy",
                                             top_k=args.top_k))

    batch_end_callbacks = [mx.callback.Speedometer(args.batch_size,
                                                   args.disp_batches)]

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
              **kwargs)
