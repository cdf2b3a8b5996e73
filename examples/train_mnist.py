#!/usr/bin/env python
"""MNIST MLP/LeNet through the Module API — the reference's canonical
``example/image-classification/train_mnist.py`` flow. Uses the synthetic
MNIST source when no dataset is present (zero-egress environment)."""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--network", default="mlp", choices=["mlp", "lenet"])
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--num-epochs", type=int, default=2)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--kv-store", default="local")
    args = p.parse_args()

    from mxtpu import compile_cache
    compile_cache.place()      # before the first jit

    import mxtpu as mx
    from mxtpu import gluon, io
    from mxtpu.gluon import nn
    from mxtpu.module import Module

    flat = args.network == "mlp"
    train = io.MNISTIter(batch_size=args.batch_size, flat=flat)
    val = io.MNISTIter(batch_size=args.batch_size, flat=flat, seed=7)  # held out

    if args.network == "mlp":
        net = nn.HybridSequential()
        net.add(nn.Dense(128, activation="relu"),
                nn.Dense(64, activation="relu"), nn.Dense(10))
    else:
        from mxtpu.gluon.model_zoo import vision
        net = vision.lenet(classes=10)

    mod = Module(net)
    mod.fit(train, eval_data=val, optimizer="sgd",
            optimizer_params={"learning_rate": args.lr, "momentum": 0.9},
            kvstore=args.kv_store, num_epoch=args.num_epochs,
            batch_end_callback=mx.callback.Speedometer(args.batch_size, 50))
    acc = dict(mod.score(val, "acc"))["accuracy"]
    print(f"final validation accuracy: {acc:.4f}")


if __name__ == "__main__":
    main()
