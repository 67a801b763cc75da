#!/usr/bin/env python
"""ImageNet training CLI — the canonical consumer of the full stack.

Re-design of the reference example (examples/imagenet/main_amp.py:1-543):
amp opt levels + fused optimizer + dynamic loss scale + (Sync)BN + data
parallelism + checkpoint/resume + train/eval loops with prec@1/prec@5 and
images/sec — driven end-to-end from one command.

Usage (synthetic data, one device):
    python examples/imagenet/main_amp.py --arch resnet50 --epochs 1 \
        --steps-per-epoch 20 --opt-level O2 --optimizer lamb

Data-parallel over an emulated 8-device CPU mesh:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python examples/imagenet/main_amp.py --n-devices 8 --sync_bn ...

A directory dataset (ImageFolder layout) is used when --data points at one
and torchvision is importable; otherwise synthetic batches (the reference
requires a real ImageNet tree — synthetic keeps the example runnable
anywhere).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import amp, checkpoint as ckpt, optimizers
from apex_tpu.models import ResNet, ResNetConfig, resnet18_config, resnet50_config
from apex_tpu.ops import softmax_cross_entropy_loss
from apex_tpu.utils import configure_compile_cache

ARCHS = {
    "resnet18": resnet18_config,
    "resnet50": resnet50_config,
    # tiny config for smoke tests
    "resnet_tiny": lambda **kw: ResNetConfig(block_sizes=(1, 1), width=8, **kw),
}


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="apex_tpu ImageNet training")
    p.add_argument("--data", default="synthetic",
                   help="'synthetic' or an ImageFolder directory")
    p.add_argument("--arch", default="resnet50", choices=sorted(ARCHS))
    p.add_argument("--epochs", type=int, default=90)
    p.add_argument("--start-epoch", type=int, default=0)
    p.add_argument("-b", "--batch-size", type=int, default=64,
                   help="global batch size")
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", type=float, default=1e-4)
    p.add_argument("--optimizer", default="sgd",
                   choices=["sgd", "adam", "lamb"])
    p.add_argument("--print-freq", type=int, default=10)
    p.add_argument("--resume", default="", help="checkpoint dir to resume from")
    p.add_argument("--evaluate", action="store_true")
    p.add_argument("--opt-level", default="O0",
                   choices=["O0", "O1", "O2", "O3"])
    p.add_argument("--keep-batchnorm-fp32", default=None, type=lambda s: s == "True")
    p.add_argument("--loss-scale", default=None,
                   help="'dynamic' or a float; default per opt level")
    p.add_argument("--sync_bn", action="store_true",
                   help="BN stats over the data-parallel axis")
    p.add_argument("--n-devices", type=int, default=1,
                   help="data-parallel width")
    p.add_argument("--steps-per-epoch", type=int, default=100,
                   help="synthetic-data epoch length")
    p.add_argument("--eval-steps", type=int, default=10)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--save-dir", default="",
                   help="checkpoint directory ('' = no checkpoints)")
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args(argv)


class AverageMeter:
    """Reference main_amp.py AverageMeter."""

    def __init__(self):
        self.reset()

    def reset(self):
        self.val = self.sum = self.count = 0.0

    def update(self, val, n=1):
        self.val = val
        self.sum += val * n
        self.count += n

    @property
    def avg(self):
        return self.sum / max(self.count, 1)


def accuracy(logits, target, topk=(1,)):
    """prec@k (reference main_amp.py:398-410)."""
    res = []
    order = jnp.argsort(logits, axis=-1)[:, ::-1]
    for k in topk:
        correct = (order[:, :k] == target[:, None]).any(axis=1)
        res.append(float(correct.mean()) * 100.0)
    return res


def make_batcher(args):
    """Synthetic, native-record, or directory input pipeline."""
    if args.data != "synthetic" and os.path.isdir(args.data):
        import glob
        if glob.glob(os.path.join(args.data, "train*.rec")):
            return _native_records_batcher(args)
        if glob.glob(os.path.join(args.data, "*.rec")):
            raise ValueError(
                f"{args.data} has .rec files but none matching train*.rec "
                "— the native backend expects train*.rec (+ optional "
                "val*.rec)")
        try:
            return _directory_batcher(args)
        except ImportError:
            print("torchvision unavailable — falling back to synthetic data")
    shape = (args.batch_size, args.image_size, args.image_size, 3)

    def batch(epoch, step, train=True):
        k = jax.random.fold_in(
            jax.random.PRNGKey(args.seed + (0 if train else 10_000)),
            epoch * 100_000 + step)
        x = jax.random.normal(k, shape, jnp.float32)
        y = jax.random.randint(jax.random.fold_in(k, 1),
                               (args.batch_size,), 0, args.num_classes)
        return x, y

    return batch


def _native_records_batcher(args):
    """C++ prefetching loader over packed record files (the reference's
    DALI data-backend role, examples/imagenet/main_amp.py --data-backend).

    Record layout: uint8 HWC image then int32 label; files
    ``<data>/train*.rec`` (shuffled) and ``<data>/val*.rec``
    (sequential; falls back to the train files when absent).  Produce the
    files with ``apex_tpu.data.write_records``.
    """
    import glob

    import numpy as np

    from apex_tpu.data import NativeRecordLoader

    rb = args.image_size * args.image_size * 3 + 4

    def decode(b):
        imgs = b[:, :-4].reshape(-1, args.image_size, args.image_size, 3)
        labels = b[:, -4:].copy().view(np.int32).ravel()
        x = imgs.astype(np.float32) / 255.0 * 2.0 - 1.0
        return jnp.asarray(x), jnp.asarray(labels)

    train_paths = sorted(glob.glob(os.path.join(args.data, "train*.rec")))
    val_paths = (sorted(glob.glob(os.path.join(args.data, "val*.rec")))
                 or train_paths)
    train_loader = NativeRecordLoader(train_paths, rb, args.batch_size,
                                      shuffle=True, seed=args.seed,
                                      decode=decode)
    val_loader = NativeRecordLoader(val_paths, rb, args.batch_size,
                                    shuffle=False, decode=decode)

    def batch(epoch, step, train=True):
        return (train_loader if train else val_loader).next_batch()

    # main() closes this at exit to reap the C++ worker threads/fds
    batch.close = lambda: (train_loader.close(), val_loader.close())
    return batch


def _directory_batcher(args):
    """Reference layout (main_amp.py:205-231): <data>/train with augmented
    shuffled loading, <data>/val with deterministic resize+center-crop. A
    flat ImageFolder dir is used for both splits if train/ is absent."""
    import torch
    import torchvision.datasets as datasets
    import torchvision.transforms as transforms

    traindir = os.path.join(args.data, "train")
    valdir = os.path.join(args.data, "val")
    if not os.path.isdir(traindir):
        traindir = valdir = args.data

    def make_loader(path, train):
        if train:
            tf = transforms.Compose([
                transforms.RandomResizedCrop(args.image_size),
                transforms.RandomHorizontalFlip(),
                transforms.ToTensor(),
            ])
        else:
            tf = transforms.Compose([
                transforms.Resize(int(args.image_size * 1.14)),
                transforms.CenterCrop(args.image_size),
                transforms.ToTensor(),
            ])
        return torch.utils.data.DataLoader(
            datasets.ImageFolder(path, tf), batch_size=args.batch_size,
            shuffle=train, drop_last=True)

    loaders = {True: make_loader(traindir, True),
               False: make_loader(valdir, False)}
    its = {True: iter(loaders[True]), False: iter(loaders[False])}

    def batch(epoch, step, train=True):
        try:
            x, y = next(its[train])
        except StopIteration:
            its[train] = iter(loaders[train])
            x, y = next(its[train])
        return (jnp.asarray(x.numpy()).transpose(0, 2, 3, 1),
                jnp.asarray(y.numpy()))

    return batch


def build(args):
    bn_axis = "data" if (args.sync_bn and args.n_devices > 1) else None
    model = ResNet(ARCHS[args.arch](num_classes=args.num_classes,
                                    bn_axis_name=bn_axis))
    params, bn_state = model.init(jax.random.PRNGKey(args.seed))

    loss_scale = args.loss_scale
    if isinstance(loss_scale, str) and loss_scale != "dynamic":
        loss_scale = float(loss_scale)
    amp_state = amp.initialize(args.opt_level, loss_scale=loss_scale,
                               keep_batchnorm_fp32=args.keep_batchnorm_fp32)

    if args.optimizer == "sgd":
        opt = optimizers.FusedSGD(lr=args.lr, momentum=args.momentum,
                                  weight_decay=args.weight_decay)
    elif args.optimizer == "adam":
        opt = optimizers.FusedAdam(lr=args.lr, weight_decay=args.weight_decay)
    else:
        opt = optimizers.FusedLAMB(lr=args.lr, weight_decay=args.weight_decay)

    state = ckpt.TrainState.create(
        params, opt.init(params), amp_state.scaler.init(), bn_state)
    return model, amp_state, opt, state


def make_train_step(model, amp_state, opt, args):
    scaler = amp_state.scaler

    def loss_fn(p, bn, x, y):
        logits, new_bn = model.apply(p, bn, x, training=True)
        return softmax_cross_entropy_loss(
            logits.astype(jnp.float32), y).mean(), (new_bn, logits)

    grad_fn = amp.scaled_value_and_grad(loss_fn, scaler, has_aux=True)

    def step_body(state, x, y):
        half = amp_state.cast_model(state.params)
        (loss, (new_bn, logits)), grads, finite = grad_fn(
            state.scaler_state, half, state.model_state,
            amp_state.cast_inputs(x), y)
        if args.n_devices > 1:
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, "data"), grads)
            finite = jax.lax.pmin(finite.astype(jnp.int32), "data") > 0
            loss = jax.lax.pmean(loss, "data")
        new_params, new_opt = opt.step(grads, state.opt_state, state.params)
        params, opt_state = amp.skip_or_step(
            finite, (new_params, new_opt), (state.params, state.opt_state))
        new_state = state.replace(
            step=state.step + 1, params=params, opt_state=opt_state,
            scaler_state=scaler.update(state.scaler_state, finite),
            model_state=new_bn)
        return new_state, loss, logits

    if args.n_devices > 1:
        from jax.experimental.shard_map import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.asarray(jax.devices()[: args.n_devices]), ("data",))
        return jax.jit(shard_map(
            step_body, mesh=mesh,
            in_specs=(P(), P("data"), P("data")),
            out_specs=(P(), P(), P("data")),
            check_rep=False))
    return jax.jit(step_body)


def make_eval_step(model, amp_state, args):
    def eval_body(state, x, y):
        half = amp_state.cast_model(state.params)
        logits, _ = model.apply(half, state.model_state,
                                amp_state.cast_inputs(x), training=False)
        loss = softmax_cross_entropy_loss(logits.astype(jnp.float32), y).mean()
        return loss, logits

    return jax.jit(eval_body)


def train_epoch(epoch, state, step_fn, batcher, args):
    batch_time, losses, top1, top5 = (AverageMeter() for _ in range(4))
    end = time.time()
    steps_since_print = 0
    for i in range(args.steps_per_epoch):
        x, y = batcher(epoch, i, train=True)
        state, loss, logits = step_fn(state, x, y)
        steps_since_print += 1
        if i % args.print_freq == 0:
            loss = float(loss)  # sync point, like the reference's .item()
            p1, p5 = accuracy(logits, y, topk=(1, 5))
            n = x.shape[0]
            # elapsed covers every (possibly async-queued) step since the
            # last print — reset `end` only here so img/s is honest
            batch_time.update(time.time() - end)
            losses.update(loss, n)
            top1.update(p1, n)
            top5.update(p5, n)
            speed = n * steps_since_print / max(batch_time.val, 1e-9)
            print(f"Epoch: [{epoch}][{i}/{args.steps_per_epoch}]\t"
                  f"Speed {speed:.1f} img/s\tLoss {losses.val:.4f} "
                  f"({losses.avg:.4f})\tPrec@1 {top1.val:.2f}\t"
                  f"Prec@5 {top5.val:.2f}")
            end = time.time()
            steps_since_print = 0
    return state, losses.avg


def validate(state, eval_fn, batcher, args):
    losses, top1, top5 = (AverageMeter() for _ in range(3))
    for i in range(args.eval_steps):
        x, y = batcher(0, i, train=False)
        loss, logits = eval_fn(state, x, y)
        p1, p5 = accuracy(logits, y, topk=(1, 5))
        n = x.shape[0]
        losses.update(float(loss), n)
        top1.update(p1, n)
        top5.update(p5, n)
    print(f" * Prec@1 {top1.avg:.3f} Prec@5 {top5.avg:.3f} "
          f"Loss {losses.avg:.4f}")
    return top1.avg


def main(argv=None):
    args = parse_args(argv)
    if args.batch_size % args.n_devices:
        raise ValueError("batch size must divide across devices")

    model, amp_state, opt, state = build(args)
    batcher = make_batcher(args)
    step_fn = make_train_step(model, amp_state, opt, args)
    eval_fn = make_eval_step(model, amp_state, args)

    start_epoch = args.start_epoch
    if args.resume:
        if ckpt.latest_step(args.resume) is not None:
            state, epoch_saved = ckpt.restore_checkpoint(args.resume, target=state)
            start_epoch = epoch_saved + 1
            print(f"=> resumed from '{args.resume}' (epoch {epoch_saved})")
        else:
            print(f"=> no checkpoint found at '{args.resume}'")

    try:
        if args.evaluate:
            validate(state, eval_fn, batcher, args)
            return state

        best_prec1 = 0.0
        for epoch in range(start_epoch, args.epochs):
            state, train_loss = train_epoch(epoch, state, step_fn, batcher,
                                            args)
            prec1 = validate(state, eval_fn, batcher, args)
            best_prec1 = max(best_prec1, prec1)
            if args.save_dir:
                ckpt.save_checkpoint(args.save_dir, state, step=epoch, keep=3)
                print(f"=> saved checkpoint (epoch {epoch})")
        print(f"Best Prec@1: {best_prec1:.3f}")
        return state
    finally:
        # native-record batchers expose close() to reap C++ worker threads
        getattr(batcher, "close", lambda: None)()


if __name__ == "__main__":
    configure_compile_cache()
    main()
